"""Correct replies completed in the window over the time it really took
(the window closes at its last reply)."""


def read(run):
    return run.log.rate(wrong=run.wrong)
