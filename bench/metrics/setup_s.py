"""setup_s: process start to window open: corpus, graph, PQ, uploads,
compiles and warm-up."""


def read(run):
    return run.setup_s
