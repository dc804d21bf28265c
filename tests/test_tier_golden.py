"""Fast-lane golden test for the dispatch-plane tier distribution.

Promotes benchmarks/tier_distribution.py to a regression gate: on the
fixed seeded graph in ``GOLDEN_DATASET``, ``dispatch_stats`` must report
exactly these tier counts. The values are checked in; any change to the
tier rules (solo/group/mega thresholds, the fused tier-S/tier-L split of
DESIGN.md §14, or the block-sweep count model) shows up here as an
integer diff and must be re-baselined deliberately.

Last re-baselined for JAX 0.9: ``jax_threefry_partitionable`` defaults to
True since JAX 0.5.0, which changes the seeded draws that build the golden
graph and its walk starts. The tier rule did not change; with the flag
set back to False the previous counts (solo 93, group_smem 162,
group_global 4, fused_small 3064, fused_big 600, fused_blocks 2400) are
reproduced exactly.

Re-baselined again when the exponential index sampler became one exact
form at every candidate count (the offset from the newest candidate,
``core/samplers.py``): the golden walks use the default exponential
bias, and the new form maps some of the same uniforms to the neighbouring
position. The law and the tier rule are unchanged; the previous counts
were group_smem 156, fused_small 3088, fused_big 591, fused_blocks 2364.
"""
from benchmarks.tier_distribution import golden_counts

EXPECTED = {
    "solo": 97,
    "group_smem": 157,
    "group_global": 3,
    "mega": 0,
    "fused_small": 3096,
    "fused_big": 588,
    "fused_blocks": 2352,
}


def test_tier_distribution_golden():
    got = golden_counts()
    assert got == EXPECTED, f"tier counts drifted: {got} != {EXPECTED}"
