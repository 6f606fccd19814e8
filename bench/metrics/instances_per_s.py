"""instances_per_s: instances solved over the host-clock span of the
window's whole rounds."""


def read(run):
    return sum(len(c.answers) for c in run.calls) / run.window_s
