"""Percent of the create requests the device judged: the create
requests answered (set-up included) less the server's `host_fallbacks`,
over the create requests answered. A batch that fell back was answered
by the host oracle, which is the plain reference's own code: a run that
fell back compares the reference with itself. (The shutdown record's
tier counters cannot be summed for this: a batch that escalates counts
as a fast batch and twice as a fixpoint batch.)"""


def read(context: dict):
    sent = context["window"]["create_requests_answered"]
    if not sent:
        return None
    fallbacks = context["shutdown"]["fallback_stats"]["host_fallbacks"]
    return 100.0 * (sent - fallbacks) / sent
