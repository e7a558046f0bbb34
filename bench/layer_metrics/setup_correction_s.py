"""Host seconds of the DEDUP-C correction build
(``dedup.build_correction_streaming``) in set-up."""


def read(run):
    return run.setup.get("bench.build.correction")
