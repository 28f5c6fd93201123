"""Share of the traced window in which the device ran no operation of its
own, in %: idle, or inside a host callback (``<program>/io_callback.N``: the
graph waits for host attention there).  A device runs one callback at a
time, and a callback nests inside the program's loop, so the idle seconds
plus the callback operations' seconds are that share.  At least
``device_idle_share``."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["devices"] or ctx["platform"] != "tpu":
        return None
    callback_s = sum(v for k, v in tr["ops"].items()
                     if "callback" in k.rsplit("/", 1)[-1])
    return 100.0 * (tr["window_s"] - tr["busy_s"] + callback_s) / tr["window_s"]
