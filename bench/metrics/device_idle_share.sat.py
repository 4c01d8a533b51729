"""See _device_idle_share.py."""
from _device_idle_share import read  # noqa: F401
