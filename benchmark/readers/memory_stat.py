"""One key of ``Device.memory_stats()`` on the fullest device, scaled.
args: ``key`` (``peak_bytes_in_use``), ``divide_by`` (``1073741824`` for GiB)."""


def read(run, key, divide_by=1):
    value = run.memory.get(key)
    return None if value is None else value / float(divide_by)
