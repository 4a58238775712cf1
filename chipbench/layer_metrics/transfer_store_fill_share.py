"""Percent of the device's transfer store that holds a row when the
server stops, from the shutdown record's `stores`: the rows the device
ledger counts over the capacity `start` built the store with. The
kernel's passes run over the whole store, so this says how much of what
they touch is live. Nothing where the program prints no such block (a
parent of the PR that added it)."""


def read(context: dict):
    stores = context["shutdown"].get("stores")
    if not stores:
        return None
    return 100.0 * stores["transfer_rows"] / stores["t_cap"]
