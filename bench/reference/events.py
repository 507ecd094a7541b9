"""Plain reference of what the event layer must keep, worked out from the
configuration and the payloads alone (it imports nothing of the system
under test).

* Packets: MUDP (arXiv:2208.05737, section IV) slices a payload into DATA
  packets numbered ``1..N`` of at most ``mtu - 28`` payload bytes each (20
  bytes of IP and 8 of UDP header per datagram, RFC 791 and RFC 768), at
  least one packet even for an empty payload; their payloads, in order,
  are the payload.
* Delivery: a reliable transport (``mudp``, ``tcp``) hands the receiver
  exactly the bytes the sender encoded.
* The sync barrier: every client the pool has not benched is on the
  roster; a roster client has arrived, failed or is late, never two of
  these; an update that came within the deadline of its own round is
  folded into that round, and one folded came within the deadline; with no
  deadline every client arrives or fails; the round lasts at least until
  its last folded arrival.

Each function returns the number of violations it found.
"""

from __future__ import annotations

IP_UDP_HEADER = 28


def packet_count(nbytes: int, mtu: int) -> int:
    return max(1, -(-nbytes // (mtu - IP_UDP_HEADER)))


def packets_off(data: bytes, packets: list[tuple[int, int, bytes]],
                mtu: int) -> int:
    """``packets``: (seq, total, payload) of one transfer's DATA packets."""
    n = packet_count(len(data), mtu)
    off = int(len(packets) != n)
    off += sum(seq != i + 1 or total != n
               or len(body) > mtu - IP_UDP_HEADER
               for i, (seq, total, body) in enumerate(packets))
    off += int(b"".join(body for _, _, body in packets) != data)
    return off


def sync_round_off(roster: list, expected_roster: list, arrived: list,
                   failed: list, deliveries: list[tuple[str, int]],
                   deadline_ns, duration_ns: int) -> int:
    """One sync round.  ``deliveries``: (client, ns since the round began)
    of every update of this round's sessions that reached the server."""
    off = int(sorted(roster) != sorted(expected_roster))
    on_roster = set(roster)
    off += len(arrived) - len(set(arrived)) + len(failed) - len(set(failed))
    off += len(set(arrived) - on_roster) + len(set(failed) - on_roster)
    off += len(set(arrived) & set(failed))
    came = {}
    for addr, t in deliveries:
        came[addr] = min(t, came.get(addr, t))
    for addr in arrived:
        t = came.get(addr)
        off += int(t is None or (deadline_ns is not None and t > deadline_ns))
    for addr, t in came.items():
        in_time = deadline_ns is None or t < deadline_ns
        if in_time and addr in on_roster and addr not in failed:
            off += int(addr not in arrived)
    if deadline_ns is None:
        off += len(on_roster - set(arrived) - set(failed))
    last = max((came[a] for a in arrived if a in came), default=0)
    off += int(duration_ns < last)
    return off
