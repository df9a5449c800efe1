"""Reader ``client_stat``: one statistic of the load generator's own
window accounting (``window.account``).  args: ``stat``."""


def read(ctx: dict, args: dict):
    return ctx.get("client", {}).get(args["stat"])
