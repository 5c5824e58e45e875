"""Swiss-Prot motif search: protein rows of their own.

Each row's sequence is a protein string from
``repro.data.corpora.swissprot_sequences`` (independent residues at
the UniProtKB/Swiss-Prot release composition; as lengths the
quantiles of a log-normal of mean 360, the same for every seed), and
its vector one of the label rule's clustered Gaussians at the
configuration's width (``bench/corpus.py``), standing in for the
per-protein ProtT5 embedding.  Queries are fresh draws from the same
generator, as in ``labels.py``.
"""

from repro.data.corpora import swissprot_sequences

from bench import corpus


def rows(n: int, seed: int, cfg: dict):
    return (corpus.vectors(n, cfg["dim"], seed, cfg["normalize"]),
            swissprot_sequences(n, seed))


def queries(count: int, seed: int, cfg: dict, stream: int):
    return corpus.queries(count, cfg["dim"], seed, cfg["normalize"], stream)
