"""See _busy_mfu.py."""
from _busy_mfu import read  # noqa: F401
