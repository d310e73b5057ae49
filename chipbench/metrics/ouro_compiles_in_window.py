"""Programs the backend compiled while the window was open
(``heat_tpu.telemetry.CompileWatcher`` over ``jax.monitoring``): expect 0."""


def read(reading):
    return float(reading.compiles)
