"""Share of the traced slice in which no operation ran on the device,
mean over the chips (layer: device)."""


def read(rec):
    return 100.0 * (1.0 - rec["busy_s"] / rec["window_s"])
