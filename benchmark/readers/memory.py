"""Peak device memory on the fullest chip as a share of the chip's HBM."""


def read(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    if not peak:
        return None
    return 100.0 * peak / ctx["peaks"]["hbm_bytes"]
