"""Percent of the ids the served lookups asked the account cache for
that it did not hold, from the shutdown record's `accounts` block
(`cache_misses` over `cache_hits` + `cache_misses`, the `ObjectCache`'s
own counters: each appearance of an id counts, set-up's lookup and the
read-back's included). Nothing where the program prints no such block (a
parent of the PR that added it) or no lookup was served."""


def read(context: dict):
    accounts = context["shutdown"].get("accounts")
    if not accounts:
        return None
    asked = accounts["cache_hits"] + accounts["cache_misses"]
    if not asked:
        return None
    return 100.0 * accounts["cache_misses"] / asked
