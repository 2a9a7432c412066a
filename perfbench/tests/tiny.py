"""A cell small enough for the CPU: the tests drive whole runs on it."""
import copy
import json
import os

from perfbench import spec as SP

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")

TINY_CRAWL = dict(n_domains=16, frontier_capacity=64, fetch_batch=8,
                  dispatch_capacity=64, bloom_bits_log2=16)
TINY_SEARCH = dict(rate_qps=40.0, index_prefill=8192, index_room=8192,
                   check_queries=48, warm_backlog_s=0.2)


def _read(path):
    with open(path) as f:
        return json.load(f)


def tiny_cell(traffic: str = "crawl", chips: int = 1) -> SP.Cell:
    """The cell's configuration and mix with the scale cut to the CPU."""
    cfg = copy.deepcopy(_read(os.path.join(CONFIGS, "webparf-A.json")))
    cfg["crawl"].update(TINY_CRAWL, n_domains=16 * chips)
    mix = copy.deepcopy(_read(os.path.join(TRAFFIC, traffic + ".json")))
    if "search" in mix:
        mix["search"].update(TINY_SEARCH)
    return SP.Cell(name=f"tiny.{traffic}", chips=chips, config=cfg,
                   traffic=mix, end_to_end=[], per_layer=[])
