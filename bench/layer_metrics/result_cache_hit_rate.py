"""Share of the window's requests answered from the tier's result cache
(``tier.result_stats``, the difference across the window), in %."""


def read(run):
    total = run.cache["hits"] + run.cache["misses"]
    return 100.0 * run.cache["hits"] / total if total else None
