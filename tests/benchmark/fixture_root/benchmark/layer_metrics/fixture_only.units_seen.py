"""Rounds or steps the traced window recorded: a reader added as a file."""


def read(run):
    return float(len(run.records)) or None
