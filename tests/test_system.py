"""End-to-end system tests: the full Tempest-JAX loop — streaming
ingestion -> dual-index rebuild -> cooperative walk generation ->
downstream consumers (skipgram embeddings, LM batches)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (
    EngineConfig,
    SamplerConfig,
    SchedulerConfig,
    WalkConfig,
    WindowConfig,
)
from repro.core.streaming import StreamingEngine
from repro.core.validation import validate_walks
from repro.data.synthetic import chronological_batches, powerlaw_temporal_graph
from repro.data.walk_dataset import skipgram_pairs, walks_to_lm_batch
from repro.obs import new_registry
from repro.train.embeddings import (
    init_skipgram,
    link_prediction_auc,
    train_on_walks,
)

pytestmark = pytest.mark.slow      # end-to-end streaming system + downstream consumers


def test_streaming_end_to_end():
    g = powerlaw_temporal_graph(256, 20_000, seed=31)
    cfg = EngineConfig(
        window=WindowConfig(duration=4000, edge_capacity=1 << 15,
                            node_capacity=256),
        sampler=SamplerConfig(bias="exponential", mode="weight"),
        scheduler=SchedulerConfig(path="grouped"),
    )
    reg = new_registry()
    eng = StreamingEngine(cfg, batch_capacity=4096, registry=reg)
    wcfg = WalkConfig(num_walks=1024, max_length=20, start_mode="nodes")
    seen_valid, seen_emitted = [], []

    def on_batch(e, walks):
        rep = validate_walks(e.state.index, walks)
        seen_valid.append(float(rep.walk_valid_frac))
        seen_emitted.append(int(np.sum(np.asarray(walks.lengths) >= 2)))

    stats = eng.replay(chronological_batches(g, 8), wcfg, on_batch=on_batch)
    assert len(stats.ingest_s) == 8
    assert all(v == 1.0 for v in seen_valid)           # paper §3.10
    assert int(eng.state.ingested) == 20_000
    # every sampling round publishes its emitted walks (walks that advanced
    # at least one hop) into the registry
    assert len(seen_emitted) == 8
    assert reg.histogram("stage_seconds",
                         labels={"stage": "walks.publish"}).count == 8
    assert reg.value("walks_emitted_total",
                     labels={"driver": "host"}) == sum(seen_emitted)
    assert all(0 <= v <= wcfg.num_walks for v in seen_emitted)
    assert seen_emitted[-1] > 0
    # bounded memory: active edges never exceed capacity
    assert max(stats.edges_active) <= 1 << 15


def test_walks_feed_embeddings():
    g = powerlaw_temporal_graph(128, 8000, seed=32)
    cfg = EngineConfig(
        window=WindowConfig(duration=100_000, edge_capacity=1 << 14,
                            node_capacity=128))
    eng = StreamingEngine(cfg, batch_capacity=8192)
    eng.ingest_batch(g.src, g.dst, g.ts)
    walks = eng.sample_walks(WalkConfig(num_walks=2048, max_length=10,
                                        start_mode="nodes"))
    state = init_skipgram(128, 16, jax.random.PRNGKey(0))
    state, loss = train_on_walks(state, walks.nodes, walks.lengths,
                                 jax.random.PRNGKey(1), epochs=2)
    assert np.isfinite(loss)
    auc = link_prediction_auc(state, g.src[-500:], g.dst[-500:], 128)
    # walks encode co-occurrence: better than random
    assert auc > 0.55, auc


def test_walks_feed_lm_batches():
    g = powerlaw_temporal_graph(128, 8000, seed=33)
    cfg = EngineConfig(
        window=WindowConfig(duration=100_000, edge_capacity=1 << 14,
                            node_capacity=128))
    eng = StreamingEngine(cfg, batch_capacity=8192)
    eng.ingest_batch(g.src, g.dst, g.ts)
    walks = eng.sample_walks(WalkConfig(num_walks=512, max_length=12,
                                        start_mode="nodes"))
    toks, labels = walks_to_lm_batch(np.asarray(walks.nodes),
                                     np.asarray(walks.lengths),
                                     seq_len=32, batch=4, vocab=256)
    assert toks.shape == (4, 32) and labels.shape == (4, 32)
    assert toks.max() < 256 and toks.min() >= 0
    # labels are the shifted stream
    np.testing.assert_array_equal(toks[:, 1:], labels[:, :-1])


def test_skipgram_pairs_window():
    nodes = np.asarray([[1, 2, 3, -1]], np.int32)
    lengths = np.asarray([3], np.int32)
    c, x = skipgram_pairs(nodes, lengths, window=1)
    pairs = set(zip(c.tolist(), x.tolist()))
    assert pairs == {(1, 2), (2, 1), (2, 3), (3, 2)}
