"""Walks alone: ``StreamingEngine.sample_walks_donated`` over a bulk-loaded
window, call after call, with no ingest in the window.

After each call a sample of its walks, drawn from the seed, is gathered on
the device and copied to the host without waiting; the reference judges
them once the window has closed.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

import deploy
import reference
import stream

# the bias the control walks with, for each bias a deployment states
CONTROL = {"exponential": "linear", "linear": "uniform", "uniform": "linear"}


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        w = cfg["window"]
        self.E, self.N = w["edge_capacity"], w["node_capacity"]
        self.spec = stream.stream_spec(cfg, tr["bulk_edges_per_batch"])
        self.delta = deploy.window_duration(cfg)
        self.k0 = deploy.bulk_batches(self.spec, self.delta)
        self.source = stream.source(ctx.seed, self.spec)
        self.bias = cfg["sampler"]["bias"]
        bias = CONTROL[self.bias] if ctx.control else self.bias
        self.engine_cfg = deploy.engine_config(
            cfg, ctx.seed, bias=bias, start_bias=tr["start_bias"])
        self.rng = np.random.default_rng([ctx.seed, 3])
        self.samples = []
        self.hops = 0

    def setup(self) -> None:
        from repro.configs.base import WalkConfig
        from repro.core.streaming import StreamingEngine

        tr = self.ctx.traffic
        self.wcfg = WalkConfig(num_walks=tr["walks_per_call"],
                               max_length=tr["max_length"],
                               start_mode=tr["start_mode"])
        self.engine = StreamingEngine(self.engine_cfg,
                                      batch_capacity=self.spec.edges_per_batch)
        self.engine.state = None          # free the empty window first
        self.engine.state = deploy.bulk_state(
            self.source, self.spec, self.k0, self.delta, self.E, self.N)
        res = self.engine.sample_walks_donated(self.wcfg)   # warm-up
        self._sample(res)
        self.samples.clear()

    def _sample(self, res) -> None:
        rows = jnp.asarray(np.sort(self.rng.choice(
            self.wcfg.num_walks, self.ctx.traffic["checked_walks_per_call"],
            replace=False)).astype(np.int32))
        picked = tuple(jnp.take(a, rows, axis=0)
                       for a in (res.nodes, res.times, res.lengths))
        for a in picked:
            a.copy_to_host_async()
        self.samples.append(picked)

    def measure(self, seconds: float) -> None:
        t0 = time.perf_counter()
        calls = 0
        self.call_s = []
        while True:
            t = time.perf_counter()
            with self.ctx.span("walk_call"):
                res = self.engine.sample_walks_donated(self.wcfg)
            self.call_s.append(time.perf_counter() - t)
            with self.ctx.span("sample_rows"):
                self._sample(res)
            # hops as the program counts them: sum of max(length - 1, 0);
            # the call has already brought the lengths to the host
            lengths = np.asarray(res.lengths).astype(np.int64)
            self.hops += int(np.maximum(lengths - 1, 0).sum())
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0
        self.calls = calls

    def end_to_end(self) -> dict:
        return {"walk_hops_per_s": self.hops / self.window_s}

    def counts(self) -> dict:
        return {"calls": self.calls, "hops": self.hops}

    def attempted_failed(self):
        return self.calls, 0

    def release(self) -> None:
        self.samples = [tuple(np.asarray(a) for a in s) for s in self.samples]
        self.engine = None

    def check(self):
        src, dst, ts = stream.device_edges(self.source, 0, self.spec, self.k0)
        win = reference.Window(src, dst, ts, int(ts.max()), self.delta,
                               self.E)
        del src, dst, ts
        idx = reference.WindowIndex(win)
        report = reference.WalkReport()
        rng = np.random.default_rng([self.ctx.seed, 7])
        sb = reference.BIASES.index(self.ctx.traffic["start_bias"])
        for nodes, times, lengths in self.samples:
            reference.check_walks(
                idx, report, nodes, times, lengths,
                start_mode=self.wcfg.start_mode,
                bias=reference.BIASES.index(self.bias), start_bias=sb,
                max_len=self.wcfg.max_length, rng=rng)
        return {"invalid_hops": report.invalid_hops,
                "early_stops": report.early_stops,
                "bad_starts": report.bad_starts + report.too_long,
                "ks_z": report.ks_z()}, {"checked_hops": report.hops,
                                         "checked_walks": report.walks,
                                         "ks_z_by_draw": report.ks_by_kind(),
                                         "call_s": self.call_s}
