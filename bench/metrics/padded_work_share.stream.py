"""padded_work_share.stream: logical m * n over the padded m_pad * n_pad of
the buckets the stream's instances ride in, in percent (filler lanes are
counted by lane_useful_share.stream, not here)."""


def read(run):
    logical = padded = 0
    for c in run.calls:
        for inst, a in zip(c.instances, c.answers):
            if a["bucket"] is None:
                return None
            m, n = inst.shape
            logical += m * n
            padded += a["bucket"][0] * a["bucket"][1]
    return 100.0 * logical / padded if padded else None
