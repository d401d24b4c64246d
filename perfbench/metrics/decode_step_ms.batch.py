"""Engine: device time per call of the jitted decode program in the trace (ms)."""
from perfbench import readers


def read(ctx):
    return readers.decode_step_ms(ctx)
