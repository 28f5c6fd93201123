"""Share of the window the compute lanes waited on swap joins
(EngineStats.swap_wait_time), in %."""


def read(ctx):
    return 100.0 * ctx["delta"]["swap_wait_time"] / ctx["window_s"]
