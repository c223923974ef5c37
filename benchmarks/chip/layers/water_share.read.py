"""Share of (read, view) probes the waters answered without touching the
table, from the facade's tier counters over the window:
water / (water + disk)."""


def read(run):
    water = run.tier_delta.get("water", 0)
    disk = run.tier_delta.get("disk", 0)
    return 100.0 * water / (water + disk) if water + disk else None
