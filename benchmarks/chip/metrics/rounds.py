"""Mean rounds_executed of run_until_done per job of the window: a count
the program returns with every job."""


def read(run):
    return sum(j["rounds"] for j in run.jobs) / len(run.jobs)
