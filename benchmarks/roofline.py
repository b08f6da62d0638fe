"""The least work a match needs, and the least time the chip could take.

Kept with the benchmark so that every PR computes it alike. The walk is
integer gathers, no arithmetic to speak of: its roof is HBM bandwidth.
Work is counted from what the PLAIN REFERENCE does for the walked
topics, so it reads the same whatever program implements the walk:

  bytes = trie nodes the reference visits x (one node row + one edge row)
        + matched routes x one result slot (int32)
        + walked topics x one probe row

A table's row widths are read off the resident device arrays.

On a mesh the least bytes are the same: a topic is walked on the one chip
that holds its tenant. The share is chip-seconds needed over chip-seconds
spent: the bytes over ONE chip's bandwidth, against the programs' device
time summed over every chip's plane. A chip whose shard had no row in a
batch still runs the step and its time counts, so the share reaches the
roof of all the chips only where the rows spread evenly over them.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_SLOT_BYTES = 4


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise SystemExit(f"peaks.json has no entry for device kind "
                         f"{device_kind!r}: add one with its source")
    return table[device_kind]


def walk_bytes(visited_nodes: float, matched_routes: float, topics: float,
               record_bytes: dict, probe_row_bytes: int = 64) -> float:
    node_row = record_bytes.get("route_tab") or record_bytes.get("node_tab", 0)
    edge_row = record_bytes.get("edge_tab", 0)
    return (visited_nodes * (node_row + edge_row)
            + matched_routes * RESULT_SLOT_BYTES + topics * probe_row_bytes)


def roofline_share(bytes_needed: float, device_seconds: float,
                   peaks: dict) -> float:
    """Percent of the bandwidth roof: least time over time taken."""
    return 100.0 * (bytes_needed / peaks["hbm_bytes_per_s"]) / device_seconds
