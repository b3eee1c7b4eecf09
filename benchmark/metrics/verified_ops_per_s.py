"""History ops whose verdict came back in the window, per second of it.
An op is one invocation with its completion."""


def read(run):
    done = sum(c.ops for c in run.checks if c.answer is not None)
    return done / run.window_s
