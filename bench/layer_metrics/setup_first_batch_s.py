"""Seconds of the first warm-up batch: the tier packs and uploads the
graph (``engine.to_device_packed``, exact and counts) and runs its first
executable."""


def read(run):
    return run.setup.get("bench.warm.first_step")
