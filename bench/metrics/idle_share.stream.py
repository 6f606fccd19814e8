"""idle_share.stream: 1 - (union of device operation intervals) / (traced
window), in percent, over the window's rounds."""


def read(run):
    return run.idle_share()
