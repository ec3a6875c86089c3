"""Shared by the tests: a tiny manifest over the files in tests/tiny/."""

import copy
import json
import os

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MANIFEST = {
    "workloads": [
        {"name": "bert-tiny.pretrain-32", "config": "bert-tiny", "traffic": "pretrain-32", "chips": 4}],
    "end_to_end": [
        {"name": "train.samples_per_s", "unit": "samples/s/chip"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "setup.init_s", "unit": "s", "layer": "entry points", "moves": "setup_s"},
        {"name": "device.idle.train", "unit": "%", "layer": "device",
         "moves": "train.samples_per_s"}],
}


def manifest():
    return copy.deepcopy(MANIFEST)


def run(workload, seed=5, seconds=1.0, trace=False, bench_dir=TINY, man=None):
    """One run of a tiny cell through the harness's own functions."""
    from lib import harness
    cell = harness.load_cell(man or manifest(), workload, seed, seconds, trace, ROOT, bench_dir)
    lines = []
    result = harness.run_cell(cell, lines.append)
    json.dumps(result)                       # the last line has to serialise
    return result, lines
