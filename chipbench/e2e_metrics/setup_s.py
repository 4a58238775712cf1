"""Seconds from process start to the first timed send: format, boot,
warm-up, accounts, funding, un-timed requests."""


def read(context: dict):
    return context["setup_s"]
