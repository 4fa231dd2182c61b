"""Device time of one call of the decode program (serve/programs.py) on chip 0, in ms, from the trace."""
from chipbench.readers import DECODE, program_ms


def read(run):
    return program_ms(run, DECODE)
