"""A number the harness itself took during the run (``run.names``), such as
``setup_s``. args: ``name``."""


def read(run, name):
    return run.names.get(name)
