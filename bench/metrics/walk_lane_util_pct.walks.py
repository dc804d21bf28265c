"""Share of the hop loop's lane-steps that walked a hop: the program's
walk_hops_total over its walk_lane_steps_total (every loop iteration
processes all lanes, live or not), over the run's walk calls."""
import obs_read


def read(r):
    return obs_read.lane_util_pct()
