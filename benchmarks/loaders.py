"""Configuration file -> a Node serving it, by the file's ``kind``.

``load`` resolves ``cfg["kind"]`` to the module ``benchmarks/kinds/<kind>.py``
and calls its ``load(cfg, seed, devices, rehearse)``; there is no table of
kinds beside the files. The kinds there were copied from ``bench.py``
(``make_msmarco_node``, ``make_sift_node``) and
``chip_smoke.py:width_child``; those copies are now the yardstick — later
PRs may change those two files, not these. Data is loaded as one frozen
segment a shard through the product's own structures (``InvertedField``,
``VectorColumn``, ``TpuSegment``) and appended to the shard's engine: the
only way to a deployment-sized index inside a run (``_bulk`` ingests about
1,100 documents a second). The write path is therefore not on the measured
path, and every configuration file says so.

A kind returns a ``Loaded``: the node, the index name, the pool of requests
(``pool_size``, ``request(i)``, ``path(i)``), what counts as an answer
(``answer(reply)``), how a sample of answers is held to the plain reference
over the generator's raw output (``compare(sample)``) and what takes the
program's place one precision — or one guarantee — down (``control(pool)``),
and what the roofline needs to know of the work (``work(i)``, read from the
resident arrays at run time). The base class is a top-k of hits judged by
``reference/check.py``: what the first two kinds are.
"""
from __future__ import annotations

from benchmarks import byname


class Loaded:
    node = None
    index = ""
    pool_size = 0
    reference = None
    info: dict  # sizes for the record (stderr, out file)

    def request(self, i: int) -> dict:
        raise NotImplementedError

    def path(self, i: int) -> str:
        """Where pool entry ``i`` is sent as a single request."""
        return f"/{self.index}/_search"

    def answer(self, reply: dict):
        """What of one reply is held to the reference, or None where the
        reply is no valid answer (then the search is ``unanswered``)."""
        if ("error" not in reply and not reply.get("timed_out")
                and isinstance(reply.get("hits", {}).get("hits"), list)):
            return reply["hits"]["hits"]
        return None

    def compare(self, sample: list) -> dict:
        """``sample``: (pool index, answer) pairs -> {"numbers": {name:
        value}, "faults": [the first few, in words], "compared": n}. Every
        number needs a limit in the cell's file."""
        from benchmarks.reference import check

        return check.compare(self.reference, sample, self.k)

    def control(self, pool: list) -> list:
        """The control's answers to these pool entries, as ``compare``
        takes them: the reference in the program's place, one precision
        (or one stated guarantee) down. It has to come out not correct."""
        from benchmarks.reference import check

        return check.control_answers(self.reference, pool, self.k)

    def group(self, name: str, arg) -> list:
        """Pool entries of a named sort, for a warm-up that has to send
        just those together (``warmup.batches.of`` of a traffic file). A
        kind names its own; the base class knows none."""
        raise ValueError(f"{type(self).__name__} has no group [{name}]")

    def work(self, i: int) -> dict:
        """{"flop": .., "bytes": ..} the chip cannot do without to answer
        pool query ``i`` alone (benchmarks/roofline.py adds them up)."""
        raise NotImplementedError


def _sized(cfg: dict, rehearse: bool) -> dict:
    """The configuration as run; a rehearsal swaps in its tiny sizes."""
    if not rehearse:
        return cfg
    out = dict(cfg)
    out.update(cfg["rehearsal"])
    return out


def load(cfg: dict, seed: int, devices, rehearse: bool = False) -> Loaded:
    kind = byname.module("benchmarks.kinds", cfg.get("kind"),
                         "configuration kind")
    loaded = kind.load(cfg, seed, devices, rehearse)
    if (type(loaded).control is Loaded.control
            and not callable(getattr(loaded.reference, "control", None))):
        raise ValueError(
            f"configuration kind [{cfg['kind']}] has no control: neither "
            f"its Loaded nor its reference gives one")
    return loaded
