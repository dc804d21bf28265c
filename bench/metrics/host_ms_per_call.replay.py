"""Host time per replay call in which the program does not wait on the
device: its replay.stage (stack and send the batches), replay.fetch
(bring the stats and walks back) and replay.publish (registry updates)
spans, over the window's calls."""
import obs_read

STAGES = ("replay.stage", "replay.fetch", "replay.publish")


def read(r):
    n = r.counts.get("calls", 0)
    tails = [obs_read.stage_tail(s, n) for s in STAGES]
    if not n or any(t is None for t in tails):
        return None
    return sum(sum(t) for t in tails) / n * 1e3
