"""Percent of the column flush's previous-row reads of the accounts tree
that no memtable answered, from the shutdown record's `accounts` block
(`flush_reads_from_tables` over `flush_reads`, set-up included): how
much of what a write reads by key has left the memtable for the level
tables. Nothing where the program prints no such block (a parent of the
PR that added it) or the flush read no account."""


def read(context: dict):
    accounts = context["shutdown"].get("accounts")
    if not accounts or not accounts["flush_reads"]:
        return None
    return (100.0 * accounts["flush_reads_from_tables"]
            / accounts["flush_reads"])
