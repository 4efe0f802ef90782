"""Rounds per second over the whole eval intervals of the window: spans,
evals, logging and the host seams between them, as a user's run has them.
The clock is the benchmark's own, read at every interval boundary."""


def read(obs):
    marks = obs["marks"]
    if len(marks) < 2:
        return None
    (r0, t0), (r1, t1) = marks[0], marks[-1]
    return (r1 - r0) / (t1 - t0)
