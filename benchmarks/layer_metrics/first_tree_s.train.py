"""Host seconds from ``lgb.Booster(...)`` through the warm-up ``update()``,
closed by the device: transfer, variant election, compile or cache load, and
the first tree."""


def read(run):
    return run["phases"].get("first_tree_s")
