"""Mean, over the engine steps of the window, of the KV pool's pages in
use over its capacity, read from the page allocator after each step."""
import numpy as np


def read(run):
    if not run.pages_share:
        return None
    return 100.0 * float(np.mean(run.pages_share))
