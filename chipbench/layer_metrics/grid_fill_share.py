"""Percent of the data file's grid that was held at its fullest
checkpoint, from the shutdown record's `grid`: blocks not free (written,
awaiting that checkpoint to be freed, or reserved by a running
compaction) counted before each checkpoint's frees land, the most over
the run, set-up included, over the blocks `format` gave the grid. A
reservation that finds the grid full kills the server, so this is how
far the deployment's `--grid-blocks` was from that. Nothing where the
program prints no such block (a parent of the PR that added it)."""


def read(context: dict):
    grid = context["shutdown"].get("grid")
    if not grid:
        return None
    return 100.0 * grid["held_peak"] / grid["blocks"]
