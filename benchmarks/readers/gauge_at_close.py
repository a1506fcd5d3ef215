"""The bus gauge ``args.gauge`` as it stood when the window closed
(``record["counters_close"]`` holds counters and gauges alike). A program
that has no such gauge reads nothing."""


def read(record, args, ctx):
    return (record.get("counters_close") or {}).get(args["gauge"])
