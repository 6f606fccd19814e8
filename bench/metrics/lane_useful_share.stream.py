"""lane_useful_share.stream: the iterations the stream's instances need
over the iterations their lanes run, in percent.  A bucket's lanes run
in lockstep until its slowest instance converges, and batch padding adds
filler lanes; so the denominator is, per bucket of a round, the padded
lane count times the bucket's largest iteration count.  Raises where the
entry could not learn the lane count from the scheduler, rather than
leave the metric out in silence."""
import collections


def read(run):
    useful = lanes = 0
    for c in run.calls:
        buckets = collections.defaultdict(list)
        for a in c.answers:
            if a["lanes"] is None:
                raise RuntimeError("lane_useful_share.stream: the entry "
                                   "reports no lane count for the buckets")
            buckets[a["bucket"]].append(a)
        for group in buckets.values():
            useful += sum(a["iterations"] for a in group)
            lanes += group[0]["lanes"] * max(a["iterations"] for a in group)
    return 100.0 * useful / lanes if lanes else None
