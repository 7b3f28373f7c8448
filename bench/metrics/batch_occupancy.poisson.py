"""Mean share of the slots that decode, over the decode steps of the window."""
from bench import readers


def read(ctx):
    return readers.batch_occupancy(ctx)
