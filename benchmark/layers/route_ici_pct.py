"""route_ici_pct: the two all_to_alls' share of a chip's interconnect
roofline - the bytes a device handed to them over the check
(`route_bytes` of the `final` event: static shapes times steps, its own
bucket included) times 8, over the engine's wall, over
peaks.json's ici_bits_per_s of the device - median over the window's
checks.  Bounded by bandwidth it would read near 100; far under it the
collectives cost latency and fences, not bytes."""
import json
import os

from mesh_read import median_of


def read(run):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "peaks.json")
    with open(path) as f:
        peak = json.load(f)["devices"].get(
            run["device"].get("kind"), {}).get("ici_bits_per_s")
    if not peak:
        return None

    def share(final):
        if not final.get("wall_s"):
            return None
        return 100.0 * final["route_bytes"] * 8 / final["wall_s"] / peak

    return median_of(run, share, "route_bytes")
