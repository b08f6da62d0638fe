"""Of the rows the mesh walked in the counted window, the share routed
to the fullest shard, in percent: 100 / shards is an even mesh. From the
matcher's per-tenant row counts (``query_heat``) summed by the tables'
own placement at the window's two ends; the program keeps no count a
batch, so this is the window's aggregate. ``None`` off a mesh."""


def read(ctx):
    a, b = ctx["after"].get("mesh.rows_each"), \
        ctx["before"].get("mesh.rows_each")
    if not a or not b:
        return None
    rows = [x - y for x, y in zip(a, b)]
    if sum(rows) <= 0:
        return None
    return 100.0 * max(rows) / sum(rows)
