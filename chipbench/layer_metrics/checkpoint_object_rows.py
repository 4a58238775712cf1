"""Transfer rows per checkpoint that a checkpoint's flush put through
the object path, from the shutdown record's `durable_rows` (the whole
run, set-up included): rows the per-op column path had already made
durable, if the count matches the rows created between checkpoints."""


def read(context: dict):
    rows = context["shutdown"].get("durable_rows")
    if not rows or not rows["checkpoints"]:
        return None
    return rows["object_at_checkpoint"] / rows["checkpoints"]
