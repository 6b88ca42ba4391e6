"""How many calls of a hooked span ended in the measured window: the
fixture's reader, which ``make_root.py`` adds to its root's
``benchmark/readers`` as a new file, found there by its name."""


def read(run, span):
    return len(run.records(span)) or None
