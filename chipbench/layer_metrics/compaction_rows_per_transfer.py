"""Input rows the forest's compaction jobs consumed, over the transfer
rows the device store holds when the server stops: the write
amplification compaction pays for a transfer, set-up included on both
sides. From the shutdown record's `forest.compaction.rows_in` (every
row a job read from a table of either input, in all trees) over
`stores.transfer_rows`. 0 where no tree left level 0: no job ran. With
`compact_beat_ms` it gives the time a merged row costs. Nothing where
the program prints no such block (a parent of the PR that added it) or
the store is empty."""


def read(context: dict):
    compaction = (context["shutdown"].get("forest") or {}).get("compaction")
    stores = context["shutdown"].get("stores")
    if compaction is None or not stores or not stores["transfer_rows"]:
        return None
    return compaction["rows_in"] / stores["transfer_rows"]
