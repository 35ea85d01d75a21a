"""Device time in Mosaic (Pallas) kernels — the instructions the trace
shows as `tpu_custom_call`s, whatever their names — over device busy
time, chip 0, traced window."""


def read(run):
    tr = run.trace
    if not tr or tr["busy_s_device0"] <= 0:
        return None
    return 100.0 * tr["mosaic_s"] / tr["busy_s_device0"]
