"""Rounds a dispatch of the converging limit tier ran, as a mean: the
shutdown record's `fixpoint` block, `rounds` (each dispatch's
`fix_rounds`, summed by the program as it fetches the dispatch's flags)
over `dispatches`. The whole run's, set-up included: the warm requests'
dispatches are in it beside the window's (the warm-up's own ledger is
not). Nothing where the program prints no such block (a parent of the
PR that added it) or ran no such dispatch."""


def read(context: dict):
    fixpoint = context["shutdown"].get("fixpoint")
    if not fixpoint or not fixpoint["dispatches"]:
        return None
    return fixpoint["rounds"] / fixpoint["dispatches"]
