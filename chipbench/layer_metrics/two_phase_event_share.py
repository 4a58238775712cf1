"""Percent of the transfer rows the device store holds when the server
stops that were created pending, posted or voided, from the shutdown
record's `two_phase` block (counted by the column flush, set-up
included) over `stores.transfer_rows` (which holds the warm-up's and the
set-up's rows too). It describes the traffic more than it scores the
program: 0 where a cell's file says single-phase, near 100 where every
transfer is a pending or the post of one. Nothing where the program
prints no such block (a parent of the PR that added it)."""


def read(context: dict):
    two_phase = context["shutdown"].get("two_phase")
    stores = context["shutdown"].get("stores")
    if two_phase is None or not stores or not stores["transfer_rows"]:
        return None
    rows = two_phase["pending"] + two_phase["posted"] + two_phase["voided"]
    return 100.0 * rows / stores["transfer_rows"]
