"""compile_s: seconds inside backend compile requests during set-up, from
the program's compile meter (`runtime/compile_cache.py`); requests answered
by the persistent cache cost only their load."""


def read(run):
    return run.compile.get("setup_secs")
