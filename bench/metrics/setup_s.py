"""Set-up seconds (host clock): from the top of ``bench/run.py`` to the
window's start: imports, inputs, the program's tables, compiles (cached
after a checkout's first run) and the warm-up."""


def read(run):
    return run.setup_s
