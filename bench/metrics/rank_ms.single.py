"""Host edge ranking per solve: the solver's SolveTrace.rank_us."""
from bench import readers


def read(run):
    return readers.program_ms_per(run, "rank_us", "solves")
