"""Device bytes of the slot cache the engine allocated (pool, page
tables, residual, tier metadata, counters) per token its page pool holds
(pool pages x page size): how much context one chip holds. The pool may
hold fewer tokens than slots x capacity (admission reserves pages), so
this counts the format, not how far the pool is oversubscribed."""
UNIT = "B/token"


def read(run):
    return run.cache_bytes / (run.pool_pages * run.page_size)
