"""Plain reference for a policy set of Pod templates.

K8sRequiredLabels, K8sAllowedRepos and K8sContainerLimits over Pods,
written from the templates' Rego (``configs/large-cluster-policy.json``)
in numpy and plain Python.  It imports nothing of the program.

It answers the capped audit: per constraint, the violating Pods in the
order of their cache keys (``namespace/<ns>/<apiVersion>/<kind>/<name>``),
all messages of each, taking Pods until at least ``limit`` messages are
out (the cap of Gatekeeper's audit, applied per constraint).

The configuration states float32 for the quantity compares.  The control
is this reference computed one step below, in bfloat16: every cpu and
memory quantity, and every policy maximum, rounded to bfloat16 before it
is compared.

``LIMITS`` are the limits of the numbers that decide ``correct``.  Every
comparison is exact, so every limit is 0.
"""

from __future__ import annotations

import re
import urllib.parse

import ml_dtypes
import numpy as np

LIMITS = {
    "audit_missing": 0,      # reference entries the program left out
    "audit_extra": 0,        # program entries the reference does not have
}

_NUM = re.compile(r"^[0-9]+(\.[0-9]+)?$")
_BIN = {"ki": 2**10, "mi": 2**20, "gi": 2**30, "ti": 2**40, "pi": 2**50,
        "ei": 2**60}
_DEC = {"": 1, "k": 10**3, "m": 10**6, "g": 10**9, "t": 10**12,
        "p": 10**15, "e": 10**18}
SLOTS = 3


def cpu_milli(v) -> float:
    """canonify_cpu: a number is cores, ``<n>m`` millicores, a plain
    decimal string cores; anything else is undefined (NaN)."""
    if isinstance(v, bool):
        return float("nan")
    if isinstance(v, (int, float)):
        return v * 1000
    if isinstance(v, str):
        if v.endswith("m"):
            try:
                return float(v.replace("m", ""))
            except ValueError:
                return float("nan")
        if _NUM.match(v):
            return float(v) * 1000
    return float("nan")


def mem_bytes(v) -> float:
    """canonify_mem: a number as is, else units.parse_bytes."""
    if isinstance(v, bool):
        return float("nan")
    if isinstance(v, (int, float)):
        return v
    if not isinstance(v, str):
        return float("nan")
    low = v.strip().strip('"').lower()
    i = len(low)
    while i > 0 and low[i - 1].isalpha():
        i -= 1
    num, suffix = low[:i], low[i:]
    if suffix.endswith("b"):
        suffix = suffix[:-1]
    mult = _BIN.get(suffix, _DEC.get(suffix))
    if not num or mult is None:
        return float("nan")
    try:
        return float(num) * mult
    except ValueError:
        return float("nan")


def exact(x):
    """Compared as it is: every quantity drawn is exact in float32, so
    float32 and the float64 here compare alike."""
    return x


def as_bfloat16(x):
    """``x`` rounded to bfloat16 (to nearest, ties to even)."""
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def num_str(v) -> str:
    """sprintf %v of a JSON value at the top level."""
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return str(v)


def set_str(items) -> str:
    return "{" + ", ".join(f'"{s}"' for s in sorted(items)) + "}"


def cache_key(obj: dict) -> str:
    """Gatekeeper's cache key of a namespaced object: the audit's order."""
    meta = obj["metadata"]
    return (f"namespace/{meta['namespace']}/"
            f"{urllib.parse.quote(obj['apiVersion'], safe='')}/"
            f"{obj['kind']}/{meta['name']}")


class PodState:
    """Per-row features of the live Pods, changed in place by churn.
    ``prefixes`` are the registries any constraint allows: each container
    slot keeps one bit per prefix its image starts with."""

    def __init__(self, pods: list[dict], label_names: list[str],
                 prefixes: list[str]):
        self.bit = {k: 1 << j for j, k in enumerate(label_names)}
        self.prefixes = prefixes
        self.rows: dict[str, int] = {}
        self.objs: list[dict | None] = []
        self.alive = np.zeros(0, bool)
        self.labels = np.zeros(0, np.int64)
        self.ncont = np.zeros(0, np.int64)
        self.img_bits = np.zeros((0, SLOTS), np.int64)
        self.cpu = np.zeros((0, SLOTS))
        self.mem = np.zeros((0, SLOTS))
        self.no_limits = np.zeros((0, SLOTS), bool)
        self.skeys = np.array([], dtype=str)   # cache keys, sorted
        self.srows = np.zeros(0, np.int64)     # their rows
        self.upsert(pods)

    def _grow(self, need: int) -> None:
        cap = len(self.alive)
        if need <= cap:
            return
        new = max(need, cap * 2, 1024)
        for name, fill in (("alive", False), ("labels", 0), ("ncont", 0),
                           ("img_bits", 0), ("cpu", np.nan),
                           ("mem", np.nan), ("no_limits", False)):
            a = getattr(self, name)
            b = np.full((new,) + a.shape[1:], fill, a.dtype)
            b[:cap] = a
            setattr(self, name, b)

    def upsert(self, objs: list[dict]) -> None:
        self._grow(len(self.objs) + len(objs))
        new_rows = []
        for o in objs:
            name = o["metadata"]["name"]
            r = self.rows.get(name)
            if r is None:
                r = self.rows[name] = len(self.objs)
                self.objs.append(o)
                new_rows.append(r)
            else:
                self.objs[r] = o
            self.alive[r] = True
            bits = 0
            for k in (o["metadata"].get("labels") or {}):
                bits |= self.bit.get(k, 0)
            self.labels[r] = bits
            cs = o["spec"].get("containers") or []
            if len(cs) > SLOTS:
                raise ValueError(f"{name}: more than {SLOTS} containers")
            self.ncont[r] = len(cs)
            self.img_bits[r] = 0
            self.cpu[r] = np.nan
            self.mem[r] = np.nan
            self.no_limits[r] = False
            for j, c in enumerate(cs):
                img = c.get("image")
                if isinstance(img, str):
                    self.img_bits[r, j] = sum(
                        1 << k for k, p in enumerate(self.prefixes)
                        if img.startswith(p))
                lim = (c.get("resources") or {}).get("limits")
                if lim is None or lim is False:
                    self.no_limits[r, j] = True
                elif isinstance(lim, dict):
                    self.cpu[r, j] = cpu_milli(lim.get("cpu"))
                    self.mem[r, j] = mem_bytes(lim.get("memory"))
        if new_rows:
            keys = np.array([cache_key(self.objs[r]) for r in new_rows])
            by = np.argsort(keys, kind="stable")
            # a fixed-width string array truncates what is inserted into
            # it: widen it to the longest key first
            wide = self.skeys.astype(np.promote_types(self.skeys.dtype,
                                                      keys.dtype))
            at = np.searchsorted(wide, keys[by])
            self.skeys = np.insert(wide, at, keys[by])
            self.srows = np.insert(self.srows, at,
                                   np.array(new_rows, np.int64)[by])

    def delete(self, objs: list[dict]) -> None:
        for o in objs:
            r = self.rows.get(o["metadata"]["name"])
            if r is not None:
                self.alive[r] = False
                self.objs[r] = None

    def order(self) -> np.ndarray:
        """Live rows in cache-key order."""
        return self.srows[self.alive[self.srows]]


class PodsReference:
    """The capped audit of the live Pods under the policy set, its
    quantities compared after ``q``."""

    def __init__(self, policy: dict, pods: list[dict], q=exact):
        self.constraints = policy["constraints"]
        self.q = q
        self.state = PodState(pods, sorted({
            lab for c in self.constraints if c["kind"] == "K8sRequiredLabels"
            for lab in c["spec"]["parameters"]["labels"]}), sorted({
                p for c in self.constraints if c["kind"] == "K8sAllowedRepos"
                for p in c["spec"]["parameters"]["repos"]}))

    def apply(self, upserts: list[dict], deletes: list[dict]) -> None:
        self.state.upsert(upserts)
        self.state.delete(deletes)

    def audit(self, limit: int) -> list[tuple[str, str, str, str]]:
        """(constraint name, namespace, name, msg) of the capped audit."""
        s = self.state
        order = s.order()
        slots = np.arange(SLOTS)[None, :]
        present = slots < s.ncont[:, None]
        out = []
        memo: dict = {}
        for c in self.constraints:
            kind = c["kind"]
            params = c["spec"].get("parameters") or {}
            key = (kind, repr(sorted(params.items())))
            viol = memo.get(key)
            if viol is None:
                viol = memo[key] = self._violating(kind, params, present)
            name = c["metadata"]["name"]
            emitted = 0
            for r in order[viol[order]]:
                if emitted >= limit:
                    break
                msgs = self._messages(kind, params, int(r), present)
                meta = s.objs[r]["metadata"]
                out.extend((name, meta["namespace"], meta["name"], m)
                           for m in msgs)
                emitted += len(msgs)
        return out

    def _violating(self, kind: str, params: dict,
                   present: np.ndarray) -> np.ndarray:
        s = self.state
        if kind == "K8sRequiredLabels":
            req = set(params["labels"])
            want = sum(s.bit[k] for k in req)
            return s.alive & ((s.labels & want) != want)
        if kind == "K8sAllowedRepos":
            allowed = sum(1 << s.prefixes.index(p) for p in params["repos"])
            bad = (s.img_bits & allowed) == 0
            return s.alive & (bad & present).any(axis=1)
        if kind == "K8sContainerLimits":
            q = self.q
            max_cpu = q(cpu_milli(params["cpu"]))
            max_mem = q(mem_bytes(params["memory"]))
            with np.errstate(invalid="ignore"):
                bad = (q(s.cpu) > max_cpu) | (q(s.mem) > max_mem) | \
                    s.no_limits
            return s.alive & (bad & present).any(axis=1)
        raise ValueError(f"no reference for {kind}")

    def _messages(self, kind: str, params: dict, r: int,
                  present: np.ndarray) -> list[str]:
        s = self.state
        obj = s.objs[r]
        cs = [c for j, c in enumerate(obj["spec"].get("containers") or [])
              if present[r, j]]
        if kind == "K8sRequiredLabels":
            have = set(obj["metadata"].get("labels") or {})
            return [f"you must provide labels: "
                    f"{set_str(set(params['labels']) - have)}"]
        out: set[str] = set()
        if kind == "K8sAllowedRepos":
            for c in cs:
                img = c.get("image")
                if not isinstance(img, str) or \
                        not any(img.startswith(p) for p in params["repos"]):
                    out.add(f"container <{c.get('name')}> has an invalid "
                            f"image repo <{img}>")
            return sorted(out)
        q = self.q
        max_cpu = q(cpu_milli(params["cpu"]))
        max_mem = q(mem_bytes(params["memory"]))
        for c in cs:
            lim = (c.get("resources") or {}).get("limits")
            if lim is None or lim is False:
                out.add(f"container <{c.get('name')}> has no resource limits")
                continue
            if q(cpu_milli(lim.get("cpu"))) > max_cpu:
                out.add(f"container <{c.get('name')}> cpu limit "
                        f"<{num_str(lim.get('cpu'))}> is higher than the "
                        f"maximum allowed of <{num_str(params['cpu'])}>")
            if q(mem_bytes(lim.get("memory"))) > max_mem:
                out.add(f"container <{c.get('name')}> memory limit "
                        f"<{num_str(lim.get('memory'))}> is higher than the "
                        f"maximum allowed of <{num_str(params['memory'])}>")
        return sorted(out)


def make(policy: dict, inventory: list[dict], control: bool = False):
    """The reference over ``inventory``; ``control`` computes it in
    bfloat16, as the module's docstring says."""
    return PodsReference(policy, inventory,
                         as_bfloat16 if control else exact)
