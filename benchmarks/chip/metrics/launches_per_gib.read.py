"""Decode kernel launches the coalescer issued in the window per GiB of
GET payload delivered (CoalescerStats.launches_by_kind, decode kinds)."""


def read(r):
    gib = r.counters["payload_bytes"] / float(1 << 30)
    return r.counters["decode_launches"] / gib if gib else None
