"""AOT compiles of the main path's device programs at real widths, for a
described (not attached) TPU v5e — the TPU compiler runs here and refuses
what the chip would refuse: a program that does not fit, a scatter or a
shard_map it cannot partition.  Nothing runs; a compile that passes is
not a chip run.

The topology is described inside a fixture (never at import), so every
xdist worker collects the same tests and only the worker given this file
loads the TPU library.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

R_NORTH_STAR = 1 << 20      # 1,000,000 resources -> r_pad
C_NORTH_STAR = 256          # 201 constraints in one program
R_LIBRARY = 1 << 17         # 100,000 mixed resources -> r_pad


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _specs(arrays: dict, r_pad: int, c_pad: int, sharding_of) -> tuple:
    """ShapeDtypeStructs of `arrays` with every resource axis at `r_pad`
    and every constraint axis at `c_pad` (table and element axes keep
    their built size), in sorted-name order."""
    from gatekeeper_tpu.ir.prep import binding_axes
    out = []
    for nm in sorted(arrays):
        a = arrays[nm]
        shape = tuple(r_pad if ax == "r" else c_pad if ax == "c" else d
                      for d, ax in zip(a.shape, binding_axes(nm)))
        out.append(jax.ShapeDtypeStruct(shape, a.dtype,
                                        sharding=sharding_of(nm)))
    return tuple(out)


def _library_bindings(kind: str, n: int = 256):
    from gatekeeper_tpu.api.templates import compile_target_rego
    from gatekeeper_tpu.ir.lower import lower_template
    from gatekeeper_tpu.ir.prep import build_bindings
    from gatekeeper_tpu.library import LIBRARY, constraint_doc, make_mixed
    from gatekeeper_tpu.store.table import ResourceTable
    from gatekeeper_tpu.target.k8s import K8sValidationTarget
    import random
    rego, params = LIBRARY[kind]
    compiled = compile_target_rego(kind, "admission.k8s.gatekeeper.sh", rego)
    lowered = lower_template(compiled.module, compiled.interp)
    target, table = K8sValidationTarget(), ResourceTable()
    for obj in make_mixed(random.Random(3), n):
        key, meta, doc = target.process_data(obj)
        table.upsert(key, doc, meta)
    cons = [constraint_doc(kind, f"c{i}", params) for i in range(4)]
    return lowered.program, build_bindings(lowered.spec, table, cons)


def _audit_step(program, names):
    """The single-chip capped audit: the chunked violation mask, then
    the executor's shared top-k reduce (engine/veval.py)."""
    from gatekeeper_tpu.engine.veval import (ProgramExecutor, _eval_mask,
                                             _widen_args)
    ex = ProgramExecutor.__new__(ProgramExecutor)
    ex._cache = {}

    def step(*args):
        d = dict(zip(names, _widen_args(args)))
        viol = _eval_mask(program, d)
        reduce = ex._reduce_fn(20, tuple(viol.shape),
                               tuple(d["__rank__"].shape))._raw
        return reduce(viol, d["__rank__"])
    return step


def _with_rank(bindings) -> dict:
    arrays = dict(bindings.arrays)
    arrays["__rank__"] = np.zeros((bindings.r_pad,), np.int32)
    return arrays


def test_north_star_step_compiles(one_chip):
    """K8sRequiredLabels, the north-star kind, at 1,048,576 x 256."""
    from __graft_entry__ import _workload
    program, bindings = _workload(n_resources=256, n_constraints=8)
    arrays = _with_rank(bindings)
    specs = _specs(arrays, R_NORTH_STAR, C_NORTH_STAR, lambda _: one_chip)
    compiled = jax.jit(_audit_step(program, tuple(sorted(arrays)))) \
        .lower(*specs).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30, mem


def test_dfa_regex_kind_compiles(one_chip):
    """K8sImageDigests lowers its constant regex to an in-program DFA
    scan (ops/regex_dfa); 100,000 mixed resources."""
    program, bindings = _library_bindings("K8sImageDigests")
    assert "dfa" in repr(program).lower()
    arrays = _with_rank(bindings)
    specs = _specs(arrays, R_LIBRARY, bindings.c_pad, lambda _: one_chip)
    jax.jit(_audit_step(program, tuple(sorted(arrays)))) \
        .lower(*specs).compile()


def test_devpages_scatter_compiles(one_chip):
    """ProgramExecutor._scatter_rows: a 1 % churn bucket of row records
    scattered into a resident element column of 1,048,576 rows."""
    dev = jax.ShapeDtypeStruct((R_NORTH_STAR, 4), jnp.int32,
                               sharding=one_chip)
    rows = jax.ShapeDtypeStruct((16384,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((16384, 4), jnp.int32, sharding=one_chip)
    jax.jit(lambda d, r, v: d.at[r].set(v)).lower(dev, rows, vals).compile()


def test_shard_map_audit_compiles_2x2(topo):
    """The sharded capped audit on the (c, r) = 2 x 2 mesh the driver
    builds over four chips (parallel/sharding.py)."""
    from jax.sharding import Mesh, NamedSharding

    from __graft_entry__ import _workload
    from gatekeeper_tpu.parallel.sharding import (binding_spec,
                                                  make_sharded_topk_packed)
    program, bindings = _workload(n_resources=256, n_constraints=8)
    arrays = dict(bindings.arrays)
    names = tuple(sorted(arrays))
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("c", "r"))
    specs = {nm: binding_spec(nm, arrays[nm]) for nm in names}
    fn = make_sharded_topk_packed(program, names, specs, mesh, 20,
                                  R_NORTH_STAR)
    args = _specs(arrays, R_NORTH_STAR, C_NORTH_STAR,
                  lambda nm: NamedSharding(mesh, specs[nm]))
    compiled = jax.jit(fn).lower(args).compile()
    assert "all-gather" in compiled.as_text() or \
        "all-reduce" in compiled.as_text()
