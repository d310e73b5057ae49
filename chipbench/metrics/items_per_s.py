"""Items of all completed calls over the window's length, so that the gaps
between calls count. The window runs from the first call to the return of
the last call that began before ``--seconds`` were over."""


def read(reading):
    done = sum(1 for c in reading.window.calls if c.error is None)
    return done * reading.items_per_call / reading.window.seconds
