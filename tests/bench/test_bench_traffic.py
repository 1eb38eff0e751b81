"""The traffic generator: deterministic per seed, the same work for every
seed in another order, the lead-in, and the stated distributions."""
import json
import math
from collections import Counter
from statistics import NormalDist

import numpy as np
import pytest

from bench import traffic as T

SPEC = T.load("docqa_reuse", "qwen2-1.5b")
VOCAB = 151936


def test_same_seed_same_requests():
    a = T.generate(SPEC, 1234, 30, VOCAB, rate=2.0)
    b = T.generate(SPEC, 1234, 30, VOCAB, rate=2.0)
    assert a.docs == b.docs and a.fill_prompts == b.fill_prompts
    assert [(r.due_s, r.doc, r.question, r.max_new_tokens) for r in a.requests] == \
        [(r.due_s, r.doc, r.question, r.max_new_tokens) for r in b.requests]


def _window(tr):
    return [r for r in tr.requests if r.due_s >= 0]


def _work(tr):
    return sorted((len(tr.docs[r.doc]), len(r.question), r.max_new_tokens) for r in _window(tr))


def _gaps(tr):
    due = [0.0] + [r.due_s for r in _window(tr)]
    return np.diff(due)


@pytest.mark.parametrize("other", [1235, 2**33 + 1234, -7])
def test_other_seed_same_work_other_tokens(other):
    a = T.generate(SPEC, 1234, 30, VOCAB, rate=2.0)
    b = T.generate(SPEC, other, 30, VOCAB, rate=2.0)
    assert _work(a) == _work(b)
    assert sorted(_gaps(a)) == pytest.approx(sorted(_gaps(b)))
    assert [len(d) for d in a.docs] == [len(d) for d in b.docs]
    # the seed draws the order of the requests and of the gaps
    assert [(r.doc, len(r.question)) for r in _window(a)] != [(r.doc, len(r.question)) for r in _window(b)]
    assert list(_gaps(a)) != pytest.approx(list(_gaps(b)))
    assert [r.question for r in a.requests] != [r.question for r in b.requests]
    assert a.docs != b.docs


def test_lead_in_before_the_window_from_the_same_requests():
    rate, lead_s = 2.0, SPEC["arrival"]["lead_s"]
    tr = T.generate(SPEC, 77, 30, VOCAB, rate=rate)
    lead = [r for r in tr.requests if r.due_s < 0]
    assert len(lead) == round(rate * lead_s) > 0
    assert [r.idx for r in tr.requests] == list(range(-len(lead), len(tr.requests) - len(lead)))
    assert all(-lead_s * 1.01 <= r.due_s < 0 for r in lead)
    assert tr.requests == sorted(tr.requests, key=lambda r: r.due_s)
    window = {(len(tr.docs[r.doc]), len(r.question), r.max_new_tokens) for r in _window(tr)}
    assert {(len(tr.docs[r.doc]), len(r.question), r.max_new_tokens) for r in lead} <= window
    # every seed's lead-in holds the same requests and gaps, in its own order
    other = [r for r in T.generate(SPEC, 78, 30, VOCAB, rate=rate).requests if r.due_s < 0]
    work = lambda rs: sorted((r.doc, len(r.question), r.max_new_tokens) for r in rs)
    assert work(other) == work(lead)
    assert [r.due_s for r in other] != [r.due_s for r in lead]
    span = lambda rs: np.diff([r.due_s for r in rs] + [0.0])
    assert sorted(span(other)) == pytest.approx(sorted(span(lead)))
    assert len(_window(T.generate(dict(SPEC, arrival={"process": "poisson"}), 77, 30, VOCAB,
                                  rate=rate))) == len(tr.requests) - len(lead)


def test_each_document_asked_the_stated_times():
    tr = T.generate(SPEC, 5, 40, VOCAB, rate=2.5)
    asks = SPEC["documents"]["asks_per_document"]
    counts = np.bincount([r.doc for r in _window(tr)], minlength=len(tr.docs))
    assert (counts == asks).all()
    assert tr.expected_reuses == asks
    assert len(_window(tr)) == len(tr.docs) * asks == round(2.5 * 40 / asks) * asks


def test_arrivals_poisson_at_the_rate():
    n, rate = 400, 2.0
    g = T.exponential_gaps(rate, n)
    assert g.sum() == pytest.approx(n / rate)
    # exponential: P(gap < mean) = 1 - 1/e
    assert np.mean(g < 1 / rate) == pytest.approx(1 - math.exp(-1), abs=0.01)
    tr = T.generate(SPEC, 9, n / rate, VOCAB, rate=rate)
    due = [r.due_s for r in _window(tr)]
    assert due == sorted(due) and due[-1] == pytest.approx(len(due) / rate)


@pytest.mark.parametrize("part", ["documents", "question", "output"])
def test_lengths_clipped_lognormal(part):
    dist = SPEC[part]["length"]
    x = T.quantiles(dist, 1001)
    assert x.min() >= dist["min"] and x.max() <= dist["max"]
    assert np.median(x) == dist["median"]
    for p in (0.2, 0.4, 0.6, 0.8):
        want = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(p))
        want = min(max(want, dist["min"]), dist["max"])
        assert np.quantile(x, p) == pytest.approx(want, rel=0.01, abs=1)


def test_token_ids_in_vocab_and_lengths_match():
    tr = T.generate(SPEC, 3, 20, 1000, rate=2.0)
    ids = np.concatenate([np.asarray(d) for d in tr.docs] + [np.asarray(r.question) for r in tr.requests])
    assert ids.min() >= 0 and ids.max() < 1000
    assert all(len(p) == SPEC["fill_prompt_tokens"] for p in tr.fill_prompts)


def test_cell_file_sets_the_rate():
    for cfg in ("qwen2-1.5b", "mistral-nemo-12b-8l"):
        spec = T.load("docqa_reuse", cfg)
        assert spec["arrival"]["rate_per_s"] > 0
        assert spec["documents"] == json.loads((T.TRAFFIC_DIR / "docqa_reuse.json").read_text())["documents"]


def test_longest_request_fits_max_len():
    for cfg in ("qwen2-1.5b", "mistral-nemo-12b-8l"):
        conf = json.loads((T.TRAFFIC_DIR.parent / "configs" / f"{cfg}.json").read_text())
        need = sum(SPEC[k]["length"]["max"] for k in ("documents", "question", "output")) + 32
        assert conf["engine"]["overrides"]["max_len"] == -(-need // 128) * 128



def test_documents_asked_once_are_each_their_own():
    """``asks_per_document`` 1: one document per request, the lead-in's too,
    with the lead-in's documents as long as those of the requests it copies."""
    spec = T.load("docqa_fresh", "qwen2-1.5b")
    tr = T.generate(spec, 2**33 + 5, 51, VOCAB)
    assert sorted(r.doc for r in tr.requests) == list(range(len(tr.docs)))
    assert tr.expected_reuses == 1
    lead = [r for r in tr.requests if r.due_s < 0]
    win = [r for r in tr.requests if r.due_s >= 0]
    assert lead and len(win) == len(tr.fill_prompts)
    shape = lambda r: (len(tr.docs[r.doc]), len(r.question), r.max_new_tokens)  # noqa: E731
    assert not Counter(map(shape, lead)) - Counter(map(shape, win))
    other = T.generate(spec, 7, 51, VOCAB)
    assert sorted(map(len, other.docs)) == sorted(map(len, tr.docs))
