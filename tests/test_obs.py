"""Host spans and device scopes of the fit loops (``repro.obs``): which
spans a fit opens, in what order, and which scope names reach the HLO of
the compiled chunk."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.api import FaultPolicy, InjectionCampaign, KMeans
from repro.batch.estimator import BatchedKMeans
from repro.data.blobs import make_blobs
from repro.kernels import ops


@pytest.fixture(scope="module")
def blobs():
    return make_blobs(600, 8, 4, seed=3, spread=0.5)


@pytest.fixture
def spans(monkeypatch):
    """The names of the spans opened, in order, through ``repro.obs``."""
    seen = []

    def span(name):
        seen.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(obs, "span", span)
    return seen


def _per_chunk(chunks: int, campaign: bool) -> list:
    head = ["campaign"] if campaign else []
    return ["fit", "plan"] + (head + ["dispatch", "sync"]) * chunks + ["sync"]


@pytest.mark.parametrize("fault,campaign", [
    (FaultPolicy.off(), False),
    (FaultPolicy.correct(injection=InjectionCampaign(rate=1.0)), True),
])
def test_fullbatch_fit_opens_spans_in_order(blobs, spans, fault, campaign):
    x, _ = blobs
    KMeans(4, max_iter=5, tol=0.0, sync_every=2, init="random",
           fault=fault).fit(x)
    assert spans == _per_chunk(3, campaign)


def test_batched_fit_opens_spans_in_order(blobs, spans):
    x, _ = blobs
    BatchedKMeans(4, max_iter=5, tol=0.0, sync_every=2,
                  init="random").fit(jnp.stack([x[:300], x[300:]]))
    assert spans == _per_chunk(3, False)


def test_one_sync_span_per_host_read(blobs, spans, monkeypatch):
    """The ``sync`` spans count the fit loop's host reads, one each."""
    from repro.api import estimator as est_mod
    reads = []
    real = est_mod._host_read
    monkeypatch.setattr(est_mod, "_host_read",
                        lambda v: reads.append(1) or real(v))
    x, _ = blobs
    KMeans(4, max_iter=7, tol=0.0, sync_every=3, init="random").fit(x)
    assert spans.count("sync") == len(reads) == 7 // 3 + 1 + 1


def _scopes(text: str) -> set:
    return set(re.findall(r"kmeans\.(?:step|assign|partials|update|reseed)",
                          text))


STEP_SCOPES = {"kmeans.step", "kmeans.assign", "kmeans.update",
               "kmeans.reseed"}


@pytest.mark.parametrize("backend,one_pass", [
    (None, False),          # the default two-pass backend
    ("lloyd", True),        # one-pass Pallas kernel, interpret mode
    ("lloyd_ft", True),     # one-pass ABFT kernel, interpret mode
])
def test_chunk_hlo_carries_the_step_scopes(blobs, backend, one_pass):
    x, _ = blobs
    m, f = x.shape
    km = KMeans(4, max_iter=2, backend=backend)
    params = km._resolve_params(m, f)
    inj = jnp.zeros((2, 1), jnp.int32)
    if km._backend.takes_injection:
        inj = jnp.stack([km._draw_injection(km._campaign_rng(), m, f,
                                            params)] * 2)
    text = km._chunk_fn(params, 2).lower(
        ops.plan_data(x, params), x[:4], jnp.zeros((m,), jnp.int32),
        jnp.zeros((), jnp.int32), jnp.float32(0.0), jax.random.PRNGKey(0),
        jnp.int32(0), inj).as_text(debug_info=True)
    want = STEP_SCOPES | ({"kmeans.partials"} if one_pass else set())
    assert _scopes(text) == want


@pytest.mark.parametrize("backend,one_pass", [
    (None, False),                  # the XLA analogue off the chip
    ("lloyd_batched", True),        # the Pallas kernel, interpret mode
])
def test_batched_chunk_hlo_carries_the_step_scopes(blobs, backend, one_pass):
    x, _ = blobs
    xb = jnp.stack([x[:300], x[300:]])
    bkm = BatchedKMeans(4, max_iter=2, backend=backend)
    params = bkm._resolve_params(2, 300, x.shape[1])
    plan = ops.plan_data_batched(xb, params)
    text = bkm._chunk_fn(params, 2).lower(
        plan, xb[:, :4], jnp.zeros((2, 300), jnp.int32),
        jnp.zeros((2,), jnp.float32), jnp.zeros((2,), bool),
        jnp.zeros((), jnp.int32)).as_text(debug_info=True)
    want = STEP_SCOPES | ({"kmeans.partials"} if one_pass else set())
    assert _scopes(text) == want


def test_spans_reach_the_profiler(blobs, tmp_path):
    """A fit under the profiler leaves its ``kmeans.`` spans on the host
    plane: one ``fit``, one ``plan``, a ``dispatch`` per chunk and a
    ``sync`` per host read."""
    from jax.profiler import ProfileData
    x, _ = blobs
    km = KMeans(4, max_iter=4, tol=0.0, sync_every=2, init="random")
    km.fit(x)                                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        km.fit(x)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(obs.PREFIX)]
    assert sorted(names) == sorted(
        "kmeans." + n for n in _per_chunk(2, False))
