"""Audit cycles back to back.

The mix's ``audit`` block gives each cycle's capped audit
(``limit_per_constraint``, ``full``); with a ``churn`` block each cycle
first applies one churn batch, which the configuration's generator
draws in set-up.  ``setup()`` builds everything a run pays for before
its window, ``window(seconds)`` measures, ``close()`` lets go of the
program, and ``check(reference)`` compares what the timed path returned
with the plain reference.
"""

from __future__ import annotations

import collections
import gc
import time

from harness.program import (annotate, compile_state, new_client, no_gc,
                             settle)
from harness.record import Record
from harness.seeds import SAMPLE, rng
from harness.stats import per_cycle

SPAN_RING = 4096        # the program's span ring at its default size


def result_key(r) -> tuple:
    res = r.resource or {}
    meta = res.get("metadata") or {}
    return ((r.constraint or {}).get("metadata", {}).get("name", ""),
            meta.get("namespace"), meta.get("name"), r.msg)


def multiset_gap(got: list, want: list) -> tuple[int, int]:
    """(entries of ``want`` missing from ``got``, entries of ``got`` not
    in ``want``), counted with multiplicity."""
    g, w = collections.Counter(got), collections.Counter(want)
    return sum((w - g).values()), sum((g - w).values())


class Driver:
    def __init__(self, cell, seed: int, say):
        self.cell, self.seed, self.say = cell, seed, say
        self.audit = cell.mix["audit"]
        self.churn = cell.mix.get("churn")
        self.outputs: list[list] = []         # results of each window cycle
        self.batches: list = []
        self.warm_batches = 0

    def setup(self, seconds: float) -> None:
        t = time.perf_counter()
        spec = self.cell.config["inventory"]
        gen = self.cell.generator
        with no_gc():
            self.objs = gen.make(self.seed, spec)
            if self.churn:
                c = self.churn
                # set-up runs a cold cycle, ``warmup_batches`` more, and
                # one after the background compiles: each takes a batch
                self.warm_batches = c["warmup_batches"] + 2
                self.batches = gen.churn(
                    self.seed, self.objs, self.warm_batches + c["batches"],
                    c, spec)
        self.say(f"generated {len(self.objs)} objects"
                 + (f" and {len(self.batches)} churn batches"
                    if self.batches else "")
                 + f" in {time.perf_counter() - t:.3f}s")
        t = time.perf_counter()
        self.client, self.driver = new_client(self.cell.policy, self.objs)
        self.say(f"ingest {time.perf_counter() - t:.3f}s; "
                 f"{compile_state(self.driver)}")
        # warm up this cell's own shapes: the background compiles that
        # ingest starts (a first audit racing them can fail inside the
        # program's executor), a cold cycle, the churn batches kept for
        # it, then the background compiles the cycles started
        self._next_batch = 0
        steps = [("settle", settle), ("cold cycle", self._cycle)]
        steps += [("warm cycle", self._cycle)] * max(self.warm_batches - 2, 0)
        steps += [("settle", settle), ("cycle", self._cycle),
                  ("settle", settle)]
        t0 = time.perf_counter()
        for what, step in steps:
            t = time.perf_counter()
            step()
            self.say(f"warm-up {what} {time.perf_counter() - t:.3f}s; "
                     f"{compile_state(self.driver)}")
        self.say(f"warm-up {time.perf_counter() - t0:.3f}s")
        gc.collect()
        gc.freeze()       # keep the run's long-lived data out of GC passes

    def _cycle(self):
        if self.churn:
            if self._next_batch >= len(self.batches):
                raise RuntimeError("the churn batches ran out: raise "
                                   "'batches' in the mix")
            ups, dels = self.batches[self._next_batch]
            self._next_batch += 1
            with annotate("bench.churn_apply"):
                t = time.perf_counter()
                self.client.add_data_batch(ups)
                for d in dels:
                    self.client.remove_data(d)
                self._upsert = time.perf_counter() - t
        with annotate("bench.audit"):
            return self.client.audit(
                limit_per_constraint=self.audit["limit_per_constraint"],
                full=self.audit["full"]).results()

    def window(self, seconds: float) -> Record:
        rec = Record()
        ex = self.driver.executor
        c0, u0 = ex.compiles, ex.upgrades
        self.outputs = []
        self.first_window_batch = self._next_batch
        t0 = time.perf_counter()
        end = t0 + seconds
        with annotate("bench.window"):
            while time.perf_counter() < end:
                rec.attempted += 1
                try:
                    self.outputs.append(self._cycle())
                except Exception as e:  # noqa: BLE001 - a failed cycle
                    rec.failed += 1     # counts; the run goes on
                    self.outputs.append(None)
                    self.say(f"cycle failed: {e!r}")
                if self.churn:
                    rec.upsert_s.append(self._upsert)
        rec.window_s = time.perf_counter() - t0
        rec.cycles = rec.attempted - rec.failed
        rec.compiles, rec.upgrades = ex.compiles - c0, ex.upgrades - u0
        rec.spans = self._window_spans(rec.attempted)
        self.metrics = {"audit_sweep_s": per_cycle(rec.window_s,
                                                   rec.cycles)}
        return rec

    def _window_spans(self, sweeps: int) -> list[dict] | None:
        """The program's spans of the window's sweeps: the last
        ``sweeps`` ``audit.sweep`` roots and their children, or None
        where the span ring (4,096 spans by default) may have dropped
        some of them."""
        from gatekeeper_tpu.obs.trace import get_tracer
        events = get_tracer().export()["traceEvents"]
        if len(events) >= SPAN_RING:
            return None
        roots = sorted((e for e in events if e["name"] == "audit.sweep"),
                       key=lambda e: e["ts"])[-sweeps:]
        if not roots or len(roots) < sweeps:
            return None
        ids = {e["args"]["span_id"] for e in roots}
        return roots + [e for e in events
                        if e["args"].get("parent_span_id") in ids]

    def close(self) -> None:
        self.client = self.driver = None

    def check(self, reference_module, control: bool = False) -> dict:
        """Compare the window's capped audits with the reference, each
        on the inventory as the cycle saw it: every cycle of a mix
        without churn (their inventory is the same), and with churn the
        last cycle and two drawn from the seed.  ``control`` puts the
        module's control in the program's place."""
        limit = self.audit["limit_per_constraint"]
        ref = reference_module.make(self.cell.policy, self.objs)
        ctl = reference_module.make(self.cell.policy, self.objs, True) \
            if control else None
        n = len(self.outputs)
        if self.churn:
            g = rng(self.seed, SAMPLE)
            picks = sorted(set(g.choice(n, min(2, n), replace=False)
                               .tolist()) | {n - 1}) if n else []
        else:
            picks = list(range(n))
        missing = extra = compared = 0
        want = got = None
        applied = 0
        for k in picks:
            if self.churn or want is None:
                upto = self.first_window_batch + k + 1
                for ups, dels in self.batches[applied:upto]:
                    for r in (ref, ctl) if ctl else (ref,):
                        r.apply(ups, dels)
                applied = upto
                want = [tuple(x) for x in ref.audit(limit)]
                if ctl:
                    got = [tuple(x) for x in ctl.audit(limit)]
            if self.outputs[k] is None:
                continue            # a failed cycle counts in ``failed``
            m, e = multiset_gap(got if ctl else [result_key(r) for r in
                                                 self.outputs[k]], want)
            missing += m
            extra += e
            compared += 1
        return {"audit_missing": missing, "audit_extra": extra,
                "cycles_compared": compared}
