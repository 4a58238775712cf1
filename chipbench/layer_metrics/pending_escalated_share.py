"""Percent of the requests of pendings whose proof-gated dispatch fell
back `limit_only` and were dispatched again, to a fixpoint tier: the
shutdown record's `judged_kinds`, `pending.escalated` over
`pending.requests` (a request is `pending` where some event is flagged
pending and none posts or voids). The whole run's, set-up's warm
requests included. Each such request pays a dispatch that judged
nothing before the one that did. Nothing where the program prints no
such block (a parent of the PR that added it) or judged no request of
pendings."""


def read(context: dict):
    pending = context["shutdown"].get("judged_kinds", {}).get("pending")
    if not pending or not pending["requests"]:
        return None
    return 100.0 * pending["escalated"] / pending["requests"]
