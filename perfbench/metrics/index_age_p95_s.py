"""95th percentile over the window's queries of the age of the index that
answered each: from the end of the newest crawl chunk folded into it to
the answer."""
import numpy as np


def read(rec):
    if not len(rec.age_s):
        return None
    return float(np.percentile(rec.age_s, 95))
