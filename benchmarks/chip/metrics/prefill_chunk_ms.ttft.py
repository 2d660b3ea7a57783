"""Mean device time of one execution of the prefill-chunk program."""

from chipbench.readers import program_ms


def read(run):
    return program_ms(run, "_chunk")
