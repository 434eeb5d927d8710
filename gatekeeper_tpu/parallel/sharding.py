"""Multi-chip sharding of the audit matrix.

The reference's audit is one single-threaded topdown query over the
whole constraints x resources cross-product (client.go:584-607,
regolib/src.go:38-52) — zero intra-evaluation parallelism (SURVEY
§2.4).  Here the matrix shards over a 2-D device mesh:

- axis ``r`` (the long axis): resource columns, element tensors,
  membership matrices and the match mask shard along resources — the
  direct analogue of sequence/context parallelism for this workload
  (SURVEY §5 "long-context"), scaling inventories past one chip's HBM
  over ICI;
- axis ``c``: per-constraint tensors (param sets, cvals, match rows)
  shard along constraints — the tensor-parallel analogue;
- lookup tables (unique-value predicates) are replicated: they are the
  small "weights" of this model.

The per-device program is exactly engine/veval.py's program evaluation;
cross-device reduction is a psum of violation counts over ``r`` plus an
all_gather + re-top-k for the first-k violating rows per constraint
(XLA collectives over ICI — no NCCL/MPI analogue needed, the compiler
inserts the collectives from shardings).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from gatekeeper_tpu.engine.veval import _eval_topk, pad_rank
from gatekeeper_tpu.ir.prep import Bindings, binding_axes
from gatekeeper_tpu.ir.program import Program


def binding_spec(name: str, arr: np.ndarray) -> P:
    """PartitionSpec for one bound array: resources shard on 'r',
    constraints on 'c', lookup tables replicate.  The axes convention
    lives in ir/prep.binding_axes (shared with the R-chunking path);
    unknown names raise there."""
    return P(*binding_axes(name))


def pad_bindings_for_mesh(bindings: Bindings, c_shards: int,
                          r_shards: int) -> Bindings:
    """Re-pad the c/r dimensions to multiples of the mesh axes."""
    def up(n, m):
        return ((n + m - 1) // m) * m

    c_pad2 = up(bindings.c_pad, c_shards)
    r_pad2 = up(bindings.r_pad, r_shards)
    if c_pad2 == bindings.c_pad and r_pad2 == bindings.r_pad:
        return bindings
    out = {}
    for name, arr in bindings.arrays.items():
        spec = binding_spec(name, arr)
        pads = []
        for d, ax in enumerate(spec):
            if ax == "r" and arr.shape[d] == bindings.r_pad:
                pads.append((0, r_pad2 - bindings.r_pad))
            elif ax == "c" and arr.shape[d] == bindings.c_pad:
                pads.append((0, c_pad2 - bindings.c_pad))
            else:
                pads.append((0, 0))
        while len(pads) < arr.ndim:
            pads.append((0, 0))
        fill = -1 if arr.dtype == np.int32 else 0    # int32 = interner ids; -1 = MISSING
        out[name] = np.pad(arr, pads, constant_values=fill)
    return Bindings(arrays=out, n_constraints=bindings.n_constraints,
                    n_resources=bindings.n_resources, c_pad=c_pad2,
                    r_pad=r_pad2, e_pads=bindings.e_pads)


def make_mesh(n_devices: int | None = None) -> Mesh:
    """2-D (c, r) mesh: r gets the larger factor (the long axis)."""
    devices = jax.devices()
    n = n_devices or len(devices)
    if len(devices) < n:
        raise ValueError(
            f"make_mesh needs {n} devices but jax.devices() has only "
            f"{len(devices)} ({devices[0].platform}); for a virtual mesh "
            f"set jax_platforms=cpu + jax_num_cpu_devices before any jax "
            f"use (see __graft_entry__.dryrun_multichip)")
    devices = np.asarray(devices[:n])
    c = 1
    for cand in (2, 4):
        if n % cand == 0 and n // cand >= 2:
            c = cand
            break
    return Mesh(devices.reshape(c, n // c), axis_names=("c", "r"))


def make_sim_mesh(n_shards: int) -> Mesh:
    """Row-only (1, n_shards) simulated mesh: a pure resource-axis
    partition, matching the Stage-6 partition-plan semantics (plans
    reason about the ``r`` split only; ``c`` stays whole).  Used by
    the plan validator and the ``GATEKEEPER_SHARDS=N`` simulated
    sweep."""
    devices = jax.devices()
    if n_shards < 1:
        raise ValueError(f"make_sim_mesh needs n_shards >= 1, "
                         f"got {n_shards}")
    if len(devices) < n_shards:
        raise ValueError(
            f"make_sim_mesh needs {n_shards} devices but jax.devices() "
            f"has only {len(devices)} ({devices[0].platform}); for a "
            f"simulated mesh set jax_platforms=cpu + "
            f"jax_num_cpu_devices={n_shards} before any jax use")
    devices = np.asarray(devices[:n_shards]).reshape(1, n_shards)
    return Mesh(devices, axis_names=("c", "r"))


def _topk_local_step(program: Program, names: tuple[str, ...], k: int,
                     r_pad: int, r_shards: int):
    """Per-shard body of the sharded capped audit."""
    r_local = r_pad // r_shards
    k_local = min(k, r_local)     # lax.top_k needs k <= axis size

    def local_step(*args):
        arrays = dict(zip(names, args))
        # per-shard evaluation rides the same chunked path as the
        # single-device engine (bounded [C, rc(, E)] intermediates when
        # the local slice exceeds R_CHUNK); scores use the GLOBAL r_pad
        # base so they stay comparable across shards.  Without a
        # caller-supplied global __rank__, per-shard ranks are local
        # offsets — shard-global order then comes from the `base` shift.
        rank_local = arrays.get("__rank__")
        cnt_l, rows_local, vals = _eval_topk(program, arrays, k_local,
                                             score_base=r_pad)
        counts = jax.lax.psum(cnt_l, "r")
        base = jax.lax.axis_index("r") * r_local
        if rank_local is None:
            # local ranks 0..r_local-1 were scored as r_pad - rank; fold
            # the shard offset in so earlier shards outrank later ones
            vals = jnp.where(vals > 0, vals - base, 0)
        rows_global = rows_local + base
        g_vals = jax.lax.all_gather(vals, "r", axis=1, tiled=True)        # [C, r*k_local]
        g_rows = jax.lax.all_gather(rows_global, "r", axis=1, tiled=True)
        k_final = min(k, g_vals.shape[1])
        top_vals, top_idx = jax.lax.top_k(g_vals, k_final)
        rows = jnp.take_along_axis(g_rows, top_idx, axis=1)
        if k_final < k:
            top_vals = jnp.pad(top_vals, ((0, 0), (0, k - k_final)))
            rows = jnp.pad(rows, ((0, 0), (0, k - k_final)))
        return counts, rows, top_vals > 0

    return local_step


def make_sharded_audit_fn(program: Program, names: tuple[str, ...],
                          specs: dict[str, P], mesh: Mesh, k: int,
                          r_pad: int):
    """Jitted multi-chip audit step: args (in `names` order, sharded per
    `specs`) -> (counts [C], rows [C, k], valid [C, k]), replicated over
    r, sharded over c."""
    local_step = _topk_local_step(program, names, k, r_pad,
                                  mesh.shape["r"])
    in_specs = tuple(specs[nm] for nm in names)
    out_specs = (P("c"), P("c", None), P("c", None))
    stepped = shard_map(local_step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)
    return jax.jit(stepped)


def _spans_processes(mesh: Mesh) -> bool:
    # single source of truth for the spanning predicate (the documented
    # anchor for cross-host collective-ordering discipline lives there)
    from gatekeeper_tpu.engine.veval import mesh_spans_processes
    return mesh_spans_processes(mesh)


def make_sharded_topk_packed(program: Program, names: tuple[str, ...],
                             specs: dict[str, P], mesh: Mesh, k: int,
                             r_pad: int):
    """Unjitted shard-mapped capped audit packing (counts, rows, valid)
    into ONE [C, 1+2k] int32 array — the multi-chip twin of the
    executor's single-device ``gk_topk`` (one fetch per kind).

    On a mesh spanning processes the packed result is additionally
    all_gathered over `c` so the output is fully replicated: every
    rank then fetches from its local replica (a c-sharded output spans
    non-addressable devices, which jax.Arrays cannot materialize).
    The gather is [C, 1+2k] int32 — trivial next to the eval."""
    local_step = _topk_local_step(program, names, k, r_pad,
                                  mesh.shape["r"])
    spans = _spans_processes(mesh)

    def packed_step(*args):
        counts, rows, valid = local_step(*args)
        packed = jnp.concatenate(
            [counts[:, None], rows, valid.astype(jnp.int32)], axis=1)
        if spans:
            packed = jax.lax.all_gather(packed, "c", axis=0, tiled=True)
        return packed

    in_specs = tuple(specs[nm] for nm in names)
    stepped = shard_map(packed_step, mesh=mesh, in_specs=in_specs,
                        out_specs=P(None, None) if spans else P("c", None),
                        check_vma=False)

    def gk_topk(args: tuple):
        return stepped(*args)
    return gk_topk


def make_sharded_mask_fn(program: Program, names: tuple[str, ...],
                         specs: dict[str, P], mesh: Mesh):
    """Unjitted shard-mapped full violation mask [C, R] (sharded over
    both mesh axes) — the multi-chip twin of the executor's ``gk_mask``
    (the capped path's under-fill fallback).

    On a process-spanning mesh the mask is all_gathered to full
    replication so every rank can fetch it locally — acceptable for
    this fallback/debug path (the serving path is the packed top-k,
    whose replicated output is [C, 1+2k], not [C, R])."""
    from gatekeeper_tpu.engine.veval import _eval_mask
    spans = _spans_processes(mesh)

    def local_step(*args):
        m = _eval_mask(program, dict(zip(names, args)))
        if spans:
            m = jax.lax.all_gather(m, "r", axis=1, tiled=True)
            m = jax.lax.all_gather(m, "c", axis=0, tiled=True)
        return m

    in_specs = tuple(specs[nm] for nm in names)
    stepped = shard_map(local_step, mesh=mesh, in_specs=in_specs,
                        out_specs=P(None, None) if spans else P("c", "r"),
                        check_vma=False)

    def gk_mask(args: tuple):
        return stepped(*args)
    return gk_mask


def run_sharded_audit(program: Program, bindings: Bindings, mesh: Mesh,
                      k: int = 20, rank: np.ndarray | None = None):
    """Convenience wrapper: pad, shard, run one audit step.  `rank`
    ([n_rows] int32, see veval.topk_reduce) orders the capped subset to
    match the scalar driver; default is raw row order."""
    if rank is not None:
        arrays = dict(bindings.arrays)
        arrays["__rank__"] = pad_rank(rank, bindings.r_pad)
        bindings = Bindings(arrays=arrays, n_constraints=bindings.n_constraints,
                            n_resources=bindings.n_resources,
                            c_pad=bindings.c_pad, r_pad=bindings.r_pad,
                            e_pads=bindings.e_pads)
    b = pad_bindings_for_mesh(bindings, mesh.shape["c"], mesh.shape["r"])
    names = tuple(sorted(b.arrays))
    specs = {nm: binding_spec(nm, b.arrays[nm]) for nm in names}
    fn = make_sharded_audit_fn(program, names, specs, mesh, k, b.r_pad)
    with mesh:
        counts, rows, valid = fn(*(b.arrays[nm] for nm in names))
    nc = bindings.n_constraints
    return (np.asarray(counts)[:nc], np.asarray(rows)[:nc],
            np.asarray(valid)[:nc])
