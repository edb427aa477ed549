"""Cache (``core/cache.py`` paged pool): the most pool pages that
admission held reserved at once in the run, over the pool's pages."""
UNIT = "fraction"
LAYER = "cache"
MOVES = "out_tok_s"


def read(run):
    return run.stats1["pages_reserved_peak"] / run.pool_pages
