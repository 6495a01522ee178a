"""Bytes a kernel must move, computed from shapes (the yardstick's own,
not the program's)."""


def transmit_bytes(frame_bytes: int) -> int:
    """One same-chip hop: the frame read once from HBM and written once
    to its new buffer."""
    return 2 * int(frame_bytes)
