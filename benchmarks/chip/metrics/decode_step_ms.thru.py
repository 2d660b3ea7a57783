"""Mean device time of one execution of the decode program (model step)."""

from chipbench.readers import program_ms


def read(run):
    return program_ms(run, "_decode")
