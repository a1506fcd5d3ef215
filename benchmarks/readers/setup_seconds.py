"""Process start to the opening of the timed window (host clock)."""


def read(record, args, ctx):
    return record.get("setup_s")
