"""Streaming replay: ``StreamingEngine.replay_device`` over a bulk-loaded
window, chunk after chunk, until the window's seconds are up.

Each call ingests ``batches_per_call`` batches of the stream and walks
after each batch; its walks of the last batch come back to the host. The
next chunk is generated on the device while the current one runs.
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
        self.B = tr["edges_per_batch"]
        self.K = tr["batches_per_call"]
        self.spec = stream.stream_spec(cfg, self.B)
        self.delta = deploy.window_duration(cfg)
        self.k0 = deploy.bulk_batches(self.spec, self.delta)
        self.source = stream.source(ctx.seed, self.spec)
        self.bias = cfg["sampler"]["bias"]
        # the control states the deployment's bias but walks another
        bias = CONTROL[self.bias] if ctx.control else self.bias
        self.engine_cfg = deploy.engine_config(cfg, ctx.seed, bias=bias)
        self.sent = []        # every streamed batch, in arrival order
        self.calls = []       # (stats, walks, seconds) per timed call

    def _chunk(self, first: int):
        if (first + self.K) * self.spec.span >= 2 ** 31:
            raise ValueError("the stream's timestamps would pass int32")
        return stream.batches(self.source, first, self.spec, self.K)

    def setup(self) -> None:
        from repro.configs.base import WalkConfig
        from repro.core.streaming import StreamingEngine

        tr = self.ctx.traffic
        self.wcfg = WalkConfig(num_walks=tr["walks_per_batch"],
                               max_length=tr["max_length"],
                               start_mode=tr["start_mode"])
        self.engine = StreamingEngine(self.engine_cfg, batch_capacity=self.B)
        self.engine.state = None          # free the empty window first
        self.engine.state = deploy.bulk_state(
            self.source, self.spec, self.k0, self.delta, self.E, self.N)
        self.next = self.k0
        warm = stream.to_host(self._chunk(self.next))
        self.next += self.K
        self.sent += warm
        self.warm = self.engine.replay_device(warm, self.wcfg,
                                              return_walks=True)
        self.ready = stream.to_host(self._chunk(self.next))

    def measure(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while True:
            batches = self.ready
            self.next += self.K
            with self.ctx.span("generate"):
                upcoming = self._chunk(self.next)   # runs before the call
            with self.ctx.span("replay_call"):
                out = self.engine.replay_device(batches, self.wcfg,
                                                return_walks=True)
            self.sent += batches
            self.calls.append(out)
            if time.perf_counter() - t0 >= seconds:
                break
            with self.ctx.span("stage_chunk"):
                self.ready = stream.to_host(upcoming)
        self.window_s = time.perf_counter() - t0

    # -- results -----------------------------------------------------------

    def end_to_end(self) -> dict:
        edges = self.K * self.B * len(self.calls)
        return {"replay_edges_per_s": edges / self.window_s}

    def counts(self) -> dict:
        return {"batches": self.K * len(self.calls),
                "calls": len(self.calls)}

    def attempted_failed(self):
        """Batches ingested in the window, and those that lost edges."""
        prev = self.warm[0]
        lost = np.concatenate([[prev.late_drops[-1] + prev.overflow_drops[-1]]]
                              + [s.late_drops + s.overflow_drops
                                 for s, _, _ in self.calls])
        return self.K * len(self.calls), int(np.sum(np.diff(lost) > 0))

    def release(self) -> None:
        """Keep the final store and free the rest of the program's state."""
        store = self.engine.state.index.store
        self.final_store = (store.src, store.dst, store.ts,
                            int(store.num_edges))
        self.final_t_now = int(self.engine.state.t_now)
        self.engine = None

    def check(self):
        """The numbers compared with the reference, and what it saw."""
        streamed = len(self.sent)
        src, dst, ts = stream.device_edges(self.source, 0, self.spec,
                                           self.k0 + streamed)
        n_bulk = self.k0 * self.B

        # every batch's counters, warm-up call included
        stats = [self.warm[0]] + [s for s, _, _ in self.calls]
        got = np.stack([np.concatenate([getattr(s, f) for s in stats])
                        for f in ("edges_active", "t_now", "ingested",
                                  "late_drops", "overflow_drops")])
        want = self._reference_counters(jnp.sort(ts), n_bulk)
        stats_mismatch = int(np.count_nonzero(got != want))

        t_now = int(want[1, -1])
        final = reference.Window(src, dst, ts, t_now, self.delta, self.E)
        mismatch = reference.window_mismatch(self.final_store, final)
        mismatch += int(self.final_t_now != t_now)
        self.final_store = None

        # the walks of one timed call, drawn from the seed, against the
        # window they were walked on
        rng = np.random.default_rng([self.ctx.seed, 7])
        c = int(rng.integers(len(self.calls)))
        if c < len(self.calls) - 1:
            # edges streamed after call c are masked out, not cut off, so
            # that the reference compiles one shape for both windows
            end = n_bulk + self.B * self.K * (c + 2)
            early = jnp.where(jnp.arange(ts.shape[0]) < end, ts,
                              jnp.iinfo(jnp.int32).min)
            final = reference.Window(src, dst, early,
                                     int(want[1, self.K * (c + 2) - 1]),
                                     self.delta, self.E)
            del early
        del src, dst, ts
        idx = reference.WindowIndex(final)
        report = reference.WalkReport()
        walks = self.calls[c][1]
        reference.check_walks(
            idx, report, walks.nodes, walks.times, walks.lengths,
            start_mode=self.wcfg.start_mode,
            bias=reference.BIASES.index(self.bias),
            max_len=self.wcfg.max_length, rng=rng)
        return {"window_mismatch": mismatch,
                "stats_mismatch": stats_mismatch,
                "invalid_hops": report.invalid_hops,
                "early_stops": report.early_stops,
                "bad_starts": report.bad_starts + report.too_long,
                "ks_z": report.ks_z()}, {
                    "checked_hops": report.hops,
                    "checked_walks": report.walks,
                    "ks_z_by_draw": report.ks_by_kind(),
                    "call_s": [secs for _, _, secs in self.calls]}

    def _reference_counters(self, sorted_ts, n_bulk: int):
        """Counters after each streamed batch: window size, t_now, edges
        ingested, late and overflow drops (cumulative)."""
        E, B, delta = self.E, self.B, self.delta
        batch_ts = [b[2] for b in self.sent]
        # batches 0..k0-1 left every edge in the window; batch k0 and later
        # hold no timestamp below their newest, so that is the
        # n_bulk-th smallest of all
        t_now = int(sorted_ts[n_bulk - 1])
        n = min(E, n_bulk)
        cuts = [max(t_now, int(bts.max())) for bts in batch_ts]
        cuts = np.maximum.accumulate(np.asarray(cuts, np.int64))
        older = np.asarray(jnp.searchsorted(
            sorted_ts, jnp.asarray(cuts - delta, jnp.int32), side="left"))
        ingested, late, overflow = n_bulk, 0, 0
        out = []
        for j, bts in enumerate(batch_ts):
            t_now = int(cuts[j])
            cut = t_now - delta
            # the window so far is a suffix of the (ts, arrival) order, and
            # so is every edge at or after the cutoff: keep the shorter
            kept = min(n, n_bulk + j * B - int(older[j]))
            fresh = int(np.sum(bts >= cut))
            late += B - fresh
            overflow += max(kept + fresh - E, 0)
            n = min(kept + fresh, E)
            ingested += B
            out.append((n, t_now, ingested, late, overflow))
        return np.asarray(out, np.int64).T
