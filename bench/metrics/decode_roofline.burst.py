"""See _decode_roofline.py."""
from _decode_roofline import read  # noqa: F401
