"""Host seconds of the program's planning in set-up."""


def read(run):
    return run.host_plan_s
