"""Percent of the traced window in which no operation ran on the device:
100 x (1 - union of device-op intervals / span)."""


def read(context: dict):
    dev, prof = context["device"], context["profile"]
    if dev is None or prof is None or prof["seconds"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / prof["seconds"])
