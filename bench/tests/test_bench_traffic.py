"""The traffic generator: determinism, the same work for every seed, and
the size distribution's mean and decomposed share."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from harness import spec, traffic  # noqa: E402


def _mix(name):
    return spec.load_json(BENCH / "traffic" / f"{name}.json")


def test_schedule_is_a_function_of_the_seed():
    mix = _mix("cnndm")
    a = traffic.schedule(mix, 2**31 + 9, 10.0, rate=20.0)
    b = traffic.schedule(mix, 2**31 + 9, 10.0, rate=20.0)
    c = traffic.schedule(mix, 2**31 + 10, 10.0, rate=20.0)
    assert a == b
    assert [r.text for r in a] != [r.text for r in c]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 3])
def test_every_seed_offers_the_same_work(seed):
    mix = _mix("cnndm")
    base = traffic.schedule(mix, 1, 30.0, rate=30.0)
    other = traffic.schedule(mix, seed, 30.0, rate=30.0)
    assert sorted(r.n for r in base) == sorted(r.n for r in other)
    gaps = lambda s: sorted(np.round(np.diff([r.due for r in s]), 9))  # noqa: E731
    assert len(base) == len(other) == 900
    assert other[-1].due < 30.0
    # same gap multiset up to the one gap the first arrival absorbs
    assert len(set(gaps(base)) ^ set(gaps(other))) <= 4


@pytest.mark.parametrize("seconds,rate", [(30.0, 8.0), (40.0, 6.0)])
def test_mean_and_decomposed_share(seconds, rate):
    """CNN/DailyMail averages 30.8 sentences (Liu & Lapata 2019, Table 1);
    a lognormal of median 26 and sigma 0.6 puts about 8% past 59.  One
    quantile per request reaches the distribution's tail."""
    mix = _mix("cnndm")
    for seed in (3, 4):
        n = np.array([r.n for r in traffic.schedule(mix, seed, seconds, rate=rate)])
        assert len(n) == 240
        assert n.mean() == pytest.approx(31.0667, abs=0.001)
        assert (n > 59).mean() == pytest.approx(20 / 240, abs=1e-9)
        assert n.min() == mix["sentences"]["min"]
        assert n.max() == 145
        assert len(set(n)) == 75
        # a longer window reaches the clip
        m = np.array([r.n for r in traffic.schedule(mix, seed, 40.0, rate=8.0)])
        assert m.mean() == pytest.approx(31.1, abs=0.05)
        assert m.max() == mix["sentences"]["max"]


def test_documents_have_their_sentence_count():
    mix = _mix("cnndm")
    for r in traffic.schedule(mix, 5, 2.0, rate=10.0):
        assert len(r.sentences) == r.n
        assert r.text == " ".join(r.sentences)


def test_encoder_lattice_covers_every_document():
    mix = _mix("cnndm")
    sched = traffic.schedule(mix, 11, 10.0, rate=20.0)
    lattice = traffic.encoder_lattice([r.n for r in sched], 2048)
    for r in sched:
        need = min(1 + sum(len(s.encode()) + 1 for s in r.sentences), 2048)
        length = traffic.bucket(need, traffic.LEN_BUCKET)
        assert min(length, 2048) in lattice
        assert traffic.bucket(r.n, traffic.SEG_BUCKET) in lattice[min(length, 2048)]
