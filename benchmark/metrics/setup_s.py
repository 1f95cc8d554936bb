"""Set-up: process start to the first timed request (loading, building,
compiling or loading the programs, warming up)."""


def read(run):
    return run.setup_s
