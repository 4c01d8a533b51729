"""See _decode_tick_ms.py."""
from _decode_tick_ms import read  # noqa: F401
