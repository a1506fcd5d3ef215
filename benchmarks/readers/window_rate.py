"""End-to-end rate: the window's work over the window's seconds (host clock,
both ends on a drained device). ``args.work`` names the record's count."""


def read(record, args, ctx):
    if not record.get("window_s"):
        return None
    return record[args["work"]] / record["window_s"]
