"""Transfers answered `created` in the window over the window's
seconds, first send to last reply (upstream's "load accepted tx/s")."""


def read(context: dict):
    w = context["window"]
    return w["created"] / w["seconds"]
