"""The deepest level of the LSM forest that holds a table when the
server stops, from the shutdown record's `forest` (0 is the level a
memtable flushes into; the trees have seven): how far down compaction
has carried the run's rows, which is what its beats and a lookup's
reads then pay for. Nothing where the program prints no such block (a
parent of the PR that added it) or no tree holds a table."""


def read(context: dict):
    forest = context["shutdown"].get("forest")
    if not forest or forest["deepest_level"] < 0:
        return None
    return forest["deepest_level"]
