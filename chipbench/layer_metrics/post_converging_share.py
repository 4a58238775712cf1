"""Percent of the requests of posts and voids that the converging limit
tier judged: the shutdown record's `judged_kinds`, `post_void`'s
requests under `by_tier.create_transfers_fixpoint` over its `requests`
(a request is `post_void` where some event posts or voids a pending and
none is itself pending). The whole run's, set-up's warm requests
included. A post adds nothing to a limit, so the fast tier could judge
these requests; the router sends them to the converging tier while the
request before them breached a limit. Nothing where the program prints
no such block (a parent of the PR that added it) or judged no such
request."""


def read(context: dict):
    posts = context["shutdown"].get("judged_kinds", {}).get("post_void")
    if not posts or not posts["requests"]:
        return None
    converging = posts["by_tier"].get("create_transfers_fixpoint", 0)
    return 100.0 * converging / posts["requests"]
