"""Rounds a dispatch of the converging limit tier ran for a request of
pendings, as a mean: the shutdown record's `judged_kinds`,
`pending.fixpoint_rounds` (each dispatch's `fix_rounds`, summed by the
program as it fetches the dispatch's flags) over
`pending.fixpoint_dispatches`. The whole run's, set-up's warm requests
included. `fixpoint_rounds` reads the same over every kind of request.
Nothing where the program prints no such block (a parent of the PR
that added it) or ran no such dispatch for a request of pendings."""


def read(context: dict):
    pending = context["shutdown"].get("judged_kinds", {}).get("pending")
    if not pending or not pending["fixpoint_dispatches"]:
        return None
    return pending["fixpoint_rounds"] / pending["fixpoint_dispatches"]
