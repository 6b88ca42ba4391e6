"""Share of the traced window in which no operation ran on the device, in
percent: 1 - union of the device's op intervals / window, mean over chips."""


def read(run):
    if not run.trace or run.trace['window_s'] <= 0:
        return None
    return 100.0 * (1.0 - run.trace['busy_s'] / run.trace['window_s'])
