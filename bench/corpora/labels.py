"""ACORN's label rule, the corpus of a configuration that names none:
clustered Gaussian rows, each with one label of ``cfg["labels"]`` drawn
uniformly as its whole sequence, and fresh query draws from the same
generator (``bench/corpus.py``)."""

from bench import corpus


def rows(n: int, seed: int, cfg: dict):
    codes = corpus.labels(n, seed, len(cfg["labels"]))
    return (corpus.vectors(n, cfg["dim"], seed, cfg["normalize"]),
            corpus.sequences(codes, cfg["labels"]))


def queries(count: int, seed: int, cfg: dict, stream: int):
    return corpus.queries(count, cfg["dim"], seed, cfg["normalize"], stream)
