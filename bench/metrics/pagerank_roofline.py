"""Roofline share of the PageRank program in the traced slice: its device
time against the work of bench/work/pagerank.py for the traced results' sizes."""

from bench.harness.readers import program_roofline


def read(run):
    work = run.spec.work("pagerank")
    n = 1 << int(run.config["graph"]["scale"])
    iters = int(run.traffic["reads"]["iters"])
    return program_roofline(run, "pagerank_coo",
                            lambda c: work(c["edges"], n, iters) if "edges" in c else None)
