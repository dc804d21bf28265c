"""The grouped-bucket hop loop's width ladder (DESIGN.md §10): it narrows
to the live lanes in quartering tiers, and emits exactly the walks, lengths
and dispatch stats of the full-width references (the lexsort regroup and
fullwalk), counts the lanes it processes, and pads every walk past its
own length whatever the donated buffers held."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SamplerConfig, SchedulerConfig, WalkConfig
from repro.core.walk_engine import (
    NODE_PAD,
    LaneParams,
    WalkBuffers,
    _tier_widths,
    generate_walk_lanes,
    generate_walks,
    generate_walks_donated,
)

W = 2048                       # three tiers: 2048, 512, 128
LADDER = SchedulerConfig(path="grouped", regroup="bucket")
REFERENCES = (SchedulerConfig(path="grouped", regroup="lexsort"),
              SchedulerConfig(path="fullwalk"))


def _same(ref, got, stats=False):
    np.testing.assert_array_equal(np.asarray(ref.nodes), np.asarray(got.nodes))
    np.testing.assert_array_equal(np.asarray(ref.times), np.asarray(got.times))
    np.testing.assert_array_equal(np.asarray(ref.lengths),
                                  np.asarray(got.lengths))
    assert int(ref.steps) == int(got.steps)
    if stats:
        np.testing.assert_array_equal(np.asarray(ref.stats),
                                      np.asarray(got.stats))


def _first(start_mode: str) -> int:
    """Nodes a walk holds when the hop loop starts."""
    return 2 if start_mode == "edges" else 1


def _ladder_lane_steps(lengths, first: int, steps: int, widths) -> int:
    """The lanes a ladder processes, from the walks alone: iteration i
    starts with the lanes whose walks reached node first + i, and runs at
    the narrowest tier whose next width those lanes still exceed."""
    lengths = np.asarray(lengths)
    total, j = 0, 0
    for i in range(steps):
        alive = int(np.sum(lengths >= first + i))
        while j + 1 < len(widths) and alive <= widths[j + 1]:
            j += 1
        total += widths[j]
    return total


def _lanes(n: int) -> LaneParams:
    """Mixed lane batch: three biases, budgets 1..12, padding lanes."""
    i = jnp.arange(n, dtype=jnp.int32)
    return LaneParams(
        start_node=jax.random.randint(jax.random.PRNGKey(5), (n,), 0, 200),
        bias=i % 3,
        start_bias=(i // 3) % 3,
        max_len=1 + i % 12,
        rid=i // 16,
        wid=i % 16,
        active=(i % 97) != 5,
    )


def test_tier_widths():
    assert _tier_widths(2048) == (2048, 512, 128)
    assert _tier_widths(1 << 18) == (1 << 18, 1 << 16, 1 << 14)
    assert _tier_widths(1 << 17) == (1 << 17, 1 << 15, 1 << 13)
    assert _tier_widths(1024) == (1024, 256)
    assert _tier_widths(512) == (512, 128)
    assert _tier_widths(1536) == (1536, 384)
    for w in (100, 128, 256, 384, 640, 768):
        assert _tier_widths(w) == (w,)
    for w in (512, 1536, 2048, 3 << 12, 1 << 20):
        widths = _tier_widths(w)
        assert len(widths) <= 3
        assert all(x % 128 == 0 for x in widths)
        assert widths[-1] >= max(w // 32, 128)


@pytest.mark.parametrize("start_mode", ("nodes", "edges", "all_nodes"))
@pytest.mark.parametrize("graph", ("small_index", "hub_index"))
def test_ladder_matches_references(request, graph, start_mode):
    """Walks, lengths, loop steps and dispatch stats equal the lexsort and
    fullwalk references bit for bit, with the ladder engaged."""
    index = request.getfixturevalue(graph)
    wcfg = WalkConfig(num_walks=W, max_length=16, start_mode=start_mode)
    scfg = SamplerConfig(bias="linear", mode="index")
    key = jax.random.PRNGKey(11)
    got = generate_walks(index, key, wcfg, scfg, LADDER, collect_stats=True)
    for ref_cfg in REFERENCES:
        ref = generate_walks(index, key, wcfg, scfg, ref_cfg,
                             collect_stats=True)
        _same(ref, got, stats=True)
        assert int(ref.lane_steps) == W * int(ref.steps)
    assert int(got.lane_steps) < W * int(got.steps)


@pytest.mark.parametrize("start_mode", ("nodes", "edges"))
def test_ladder_lane_batches_match_references(small_index, start_mode):
    """Lane batches with mixed per-lane budgets equal the references, and
    each lane walks as it does in a batch too narrow to ladder: a lane's
    walk depends on its own parameters only (the serving coalescer's
    bit-identity)."""
    wcfg = WalkConfig(num_walks=W, max_length=14, start_mode=start_mode)
    scfg = SamplerConfig(mode="index")
    key = jax.random.PRNGKey(2)
    lanes = _lanes(W)
    got = generate_walk_lanes(small_index, key, lanes, wcfg, scfg, LADDER)
    assert int(got.lane_steps) < W * int(got.steps)
    for ref_cfg in REFERENCES:
        _same(generate_walk_lanes(small_index, key, lanes, wcfg, scfg,
                                  ref_cfg), got)
    n = 128                                           # one tier
    head = generate_walk_lanes(
        small_index, key, jax.tree_util.tree_map(lambda x: x[:n], lanes),
        WalkConfig(num_walks=n, max_length=14, start_mode=start_mode),
        scfg, LADDER)
    assert int(head.lane_steps) == n * int(head.steps)
    for field in ("nodes", "times", "lengths"):
        np.testing.assert_array_equal(np.asarray(getattr(head, field)),
                                      np.asarray(getattr(got, field))[:n])


@pytest.mark.parametrize("start_mode", ("nodes", "edges"))
def test_ladder_pads_donated_garbage(small_index, start_mode):
    """Donated buffers full of stale values come back NODE_PAD at every
    column at or past each walk's length, and equal a fresh allocation."""
    wcfg = WalkConfig(num_walks=W, max_length=16, start_mode=start_mode)
    scfg = SamplerConfig(bias="exponential", mode="index")
    key = jax.random.PRNGKey(4)
    fresh = generate_walks(small_index, key, wcfg, scfg, LADDER)
    garbage = WalkBuffers(nodes=jnp.full((W, 17), 12345, jnp.int32),
                          times=jnp.full((W, 17), -777, jnp.int32))
    got = generate_walks_donated(small_index, key, garbage, wcfg, scfg,
                                 LADDER)
    _same(fresh, got)
    nodes, times = np.asarray(got.nodes), np.asarray(got.times)
    lengths = np.asarray(got.lengths)
    past = np.arange(17)[None, :] >= lengths[:, None]
    assert past.any() and (~past).any()
    assert (nodes[past] == NODE_PAD).all() and (times[past] == NODE_PAD).all()
    assert (nodes[~past] != NODE_PAD).all()


@pytest.mark.parametrize("start_mode", ("nodes", "edges"))
def test_ladder_lane_steps(small_index, start_mode):
    """``lane_steps`` is the sum of the widths the iterations ran at, as
    the walks' lengths imply them, between the hops walked and W × steps."""
    wcfg = WalkConfig(num_walks=W, max_length=40, start_mode=start_mode)
    scfg = SamplerConfig(bias="linear", mode="index")
    first = _first(start_mode)
    for seed in range(3):
        res = generate_walks(small_index, jax.random.PRNGKey(seed), wcfg,
                             scfg, LADDER)
        steps, lane_steps = int(res.steps), int(res.lane_steps)
        assert lane_steps == _ladder_lane_steps(res.lengths, first, steps,
                                                _tier_widths(W))
        hops = int(np.maximum(np.asarray(res.lengths) - first, 0).sum())
        assert 0 < hops <= lane_steps < W * steps


@pytest.mark.parametrize("num_walks", (384, 640))
def test_one_tier_when_not_a_multiple_of_512(small_index, num_walks):
    """A width that cannot quarter onto a multiple of 128 keeps the one
    full-width loop, whose lanes are W per iteration."""
    wcfg = WalkConfig(num_walks=num_walks, max_length=16, start_mode="nodes")
    scfg = SamplerConfig(bias="linear", mode="index")
    key = jax.random.PRNGKey(8)
    got = generate_walks(small_index, key, wcfg, scfg, LADDER)
    assert int(got.lane_steps) == num_walks * int(got.steps) > 0
    _same(generate_walks(small_index, key, wcfg, scfg, REFERENCES[0]), got)
