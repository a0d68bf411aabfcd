"""Seconds from the process's start to the window's start: imports, chip
start-up, making and ingesting the cohort, and warming every program."""


def read(run):
    return run.setup_s
