"""Compile + dispatch: programs built inside the measured window.  Must
read 0: every shape is warmed in set-up, and literals are hoisted
(expr/params.py), so other parameters reuse the programs."""


def read(run):
    return float(run.builds_at_end - run.builds_at_window)
