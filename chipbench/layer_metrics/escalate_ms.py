"""Milliseconds of one `execute_escalate` span: the re-dispatch of a
create request to a fixpoint tier after its proof-gated dispatch fell
back `limit_only`, the dispatch that judged it inside. The mean over
the escalations that start inside a `commit_execute` of the window;
`execute_dispatch_ms` holds both dispatches of such a request, the
wasted one and this one. Nothing where the window holds no escalation,
or the program has no such span (a parent of the PR that added it)."""

from chipbench.span_children import children_in_window


def read(context: dict):
    found = children_in_window(context, "execute_escalate", "commit_execute")
    if found is None:
        return None
    return 1e3 * float(found[0].mean())
