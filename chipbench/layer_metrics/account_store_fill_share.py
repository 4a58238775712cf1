"""Percent of the device's account store that holds a row when the
server stops, from the shutdown record's `stores`: the accounts the
device ledger counts over the capacity `start` built the store with
(`--account-capacity`). The control that a cell holds the population
its configuration's file states. Nothing where the program prints no
such block."""


def read(context: dict):
    stores = context["shutdown"].get("stores")
    if not stores:
        return None
    return 100.0 * stores["account_rows"] / stores["a_cap"]
