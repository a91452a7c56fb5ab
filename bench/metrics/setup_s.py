"""setup_s: process start to the first timed operation (chip start-up,
data on the device, executables from the compile cache or compiled, and
warm-up), on the host clock."""


def read(run):
    return run.setup_s
