"""Process start to the first timed step: imports, weights from the
seed, placement, compilation or cache load, the batch pool, the first
checked steps and the warm-up."""


def read(rec):
    return rec.setup_s
