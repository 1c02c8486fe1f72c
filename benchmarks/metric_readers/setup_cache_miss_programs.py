"""Programs the persistent compilation cache did not hold: the
``compile`` stages before the window that ended in a cache write
(``cache: miss``).  0 in a warm run; the books' ``cache_missed`` names
them."""
from benchmarks.harness import startup


def read(run):
    out = startup.books(run)
    return None if out is None else len(out["cache_missed"])
