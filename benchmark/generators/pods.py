"""Pods of a large cluster, and churn batches over them.

A copy of the program's ``library/workload.make_pods``, kept here so
that the yardstick cannot move with the program, with a wider spread of
resource limits.  Every choice is drawn from numpy arrays; only the
building of the JSON-shaped dicts is a Python loop.

The configuration's ``inventory`` block gives the parameters: ``n`` Pods
over ``namespaces`` namespaces, each of ten labels at ``label_p``, 1-3
containers with images from four registries x 50 apps x 9 tags, and per
container a cpu and a memory limit.  Each limit is, with ``near_p``, a
whole number of ``unit`` drawn uniformly from ``[T, T * (1 +
near_span)]`` for a ``T`` drawn from ``near`` (the policy maxima, where
a quantity compare decides at its last bits), and otherwise one of
``values``.
"""

from __future__ import annotations

import numpy as np

from harness.seeds import CHURN, CONTENT, INVENTORY, rng

LABELS = [f"l{j}" for j in range(10)]
REPOS = ["gcr.io/org/", "docker.io/", "quay.io/team/", "ghcr.io/x/"]
SLOTS = 3


def pod_name(i: int) -> str:
    return f"pod{i:07d}"


def _quantities(g: np.random.Generator, shape: tuple, q: dict) -> list:
    common = np.asarray(q["values"])[g.integers(0, len(q["values"]), shape)]
    base = np.asarray(q["near"], np.int64)[
        g.integers(0, len(q["near"]), shape)]
    span = np.floor(base * q["near_span"]).astype(np.int64) + 1
    near = np.char.add((base + (g.random(shape) * span).astype(np.int64))
                       .astype(str), q["unit"])
    return np.where(g.random(shape) < q["near_p"], near, common).tolist()


class PodDraws:
    """The draws of ``n`` Pods: label bits, container count, and per
    container slot the registry, app, tag, cpu and memory limit."""

    def __init__(self, g: np.random.Generator, n: int, spec: dict):
        self.labels = (g.random((n, len(LABELS))) < spec["label_p"]).tolist()
        self.ncont = g.integers(1, SLOTS + 1, n).tolist()
        self.image = (g.integers(0, len(REPOS), (n, SLOTS)) * 1000
                      + g.integers(0, 50, (n, SLOTS)) * 10
                      + g.integers(0, 9, (n, SLOTS))).tolist()
        self.cpu = _quantities(g, (n, SLOTS), spec["cpu"])
        self.mem = _quantities(g, (n, SLOTS), spec["memory"])
        self.namespaces = spec["namespaces"]

    def pod(self, k: int, index: int, name: str | None = None) -> dict:
        """Draw ``k`` as the Pod numbered ``index``."""
        bits, img, cpu, mem = (self.labels[k], self.image[k], self.cpu[k],
                               self.mem[k])
        return {"apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": name or pod_name(index),
                             "namespace": f"ns{index % self.namespaces}",
                             "labels": {LABELS[b]: "v" for b in range(10)
                                        if bits[b]}},
                "spec": {"containers": [
                    {"name": f"c{j}", "image": _image(img[j]),
                     "resources": {"limits": {"cpu": cpu[j],
                                              "memory": mem[j]}}}
                    for j in range(self.ncont[k])]}}


def _image(code: int) -> str:
    return f"{REPOS[code // 1000]}app{code // 10 % 100}:{code % 10}"


def make(seed: int, spec: dict) -> list[dict]:
    """``spec["n"]`` Pods drawn from one fixed seed, named from ``seed``.

    The names are distinct numbers drawn from the seed and sorted, so
    every seed keeps the same Pods in the same cache-key order under
    other names.  That order decides which Pods a capped audit reports,
    and with them the audit's work: permuting the Pods instead changed a
    full sweep's time on one v5e chip by 4 % from seed to seed, against
    1 % between two runs of one seed."""
    n = spec["n"]
    d = PodDraws(rng(CONTENT, INVENTORY), n, spec)
    names = np.sort(rng(seed, INVENTORY).choice(10 * n, n, replace=False))
    return [d.pod(i, i, f"pod{v:08d}") for i, v in enumerate(names.tolist())]


def churn(seed: int, pods: list[dict], batches: int, mix: dict,
          spec: dict) -> list[tuple[list[dict], list[dict]]]:
    """``batches`` churn batches over ``pods``, each ``(upserts,
    deletes)``.  A batch touches ``mix["fraction"]`` of the initial row
    count: ``update`` of it redraws the labels, one image and one limit
    of a live Pod, ``create`` adds new Pods, and the rest deletes live
    Pods.  Rows are drawn uniformly from the live set, without repeats
    within a batch.  ``pods`` is not changed."""
    g = rng(seed, CHURN)
    per = max(int(round(len(pods) * mix["fraction"])), 1)
    n_upd = int(round(per * mix["update"]))
    n_new = int(round(per * mix["create"]))
    n_del = per - n_upd - n_new
    current = {i: p for i, p in enumerate(pods)}
    live = list(range(len(pods)))          # row numbers, any order
    next_index = len(pods)
    out = []
    for _ in range(batches):
        pick = g.choice(len(live), n_upd + n_del, replace=False).tolist()
        upd_rows = [live[k] for k in pick[:n_upd]]
        del_pos = sorted(pick[n_upd:], reverse=True)
        fresh = PodDraws(g, n_upd + n_new, spec)
        slots = g.integers(0, SLOTS, n_upd).tolist()
        fields = g.integers(0, 2, n_upd).tolist()
        upserts = []
        for k, row in enumerate(upd_rows):
            upserts.append(_redraw(current[row], fresh.pod(k, row),
                                   slots[k], fields[k]))
            current[row] = upserts[-1]
        for k in range(n_new):
            p = fresh.pod(n_upd + k, next_index)
            upserts.append(p)
            current[next_index] = p
            next_index += 1
        deletes = []
        for pos in del_pos:
            row = live[pos]
            old = current.pop(row)
            deletes.append({"apiVersion": "v1", "kind": "Pod",
                            "metadata": dict(old["metadata"])})
            live[pos] = live[-1]
            live.pop()
        live.extend(range(next_index - n_new, next_index))
        out.append((upserts, deletes))
    return out


def _redraw(prev: dict, drawn: dict, slot: int, field: int) -> dict:
    """``prev`` with the labels of ``drawn``, and the image and one limit
    (cpu or memory) of one container taken from ``drawn``'s first."""
    containers = [{"name": c["name"], "image": c["image"],
                   "resources": {"limits": dict(c["resources"]["limits"])}}
                  for c in prev["spec"]["containers"]]
    src = drawn["spec"]["containers"][0]
    c = containers[slot % len(containers)]
    c["image"] = src["image"]
    key = ("cpu", "memory")[field]
    c["resources"]["limits"][key] = src["resources"]["limits"][key]
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": prev["metadata"]["name"],
                         "namespace": prev["metadata"]["namespace"],
                         "labels": drawn["metadata"]["labels"]},
            "spec": {"containers": containers}}
