"""Continuous-batching server: admission, slot recycling, determinism."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs.base import ParallelConfig, get_smoke_config
from repro.models import model as M
from repro.runtime.server import Request, ServeConfig, Server


@pytest.fixture(scope="module")
def server():
    cfg = get_smoke_config("minicpm_2b")
    par = ParallelConfig(tp=1, dp=1)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    params = M.init_model(jax.random.PRNGKey(0), cfg, par)
    sc = ServeConfig(max_batch=2, max_seq=64, eos_token=-1, max_new_tokens=4)
    return Server(cfg, par, mesh, params, sc), cfg


def test_serve_more_requests_than_slots(server):
    srv, cfg = server
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               size=(3 + i,)).astype(np.int32))
            for i in range(5)]          # 5 requests, 2 slots
    done = srv.serve(reqs)
    assert len(done) == 5
    for r in done:
        assert r.done
        assert 1 <= len(r.output) <= 4
        assert all(0 <= t < cfg.vocab_size for t in r.output)


def test_greedy_determinism(server):
    srv, cfg = server
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab_size
    a = srv.serve([Request(rid=100, prompt=prompt)])[0].output
    b = srv.serve([Request(rid=101, prompt=prompt)])[0].output
    assert a == b


def test_keep_logits_and_teacher_forcing():
    """``keep_logits`` records one [vocab] row per emitted token whose argmax
    is that token; forcing a request along its own tokens reproduces both."""
    cfg = get_smoke_config("minicpm_2b")
    par = ParallelConfig(tp=1, dp=1)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    srv = Server(cfg, par, mesh, M.init_model(jax.random.PRNGKey(2), cfg, par),
                 ServeConfig(max_batch=2, max_seq=64, eos_token=-1,
                             max_new_tokens=5, prefill_chunk=8,
                             keep_logits=True))
    prompt = np.arange(11, dtype=np.int32) % cfg.vocab_size
    free = srv.serve([Request(rid=0, prompt=prompt)])[0]
    assert len(free.logits) == len(free.output) == 5
    assert all(lg.shape == (cfg.vocab_size,) for lg in free.logits)
    assert [int(np.argmax(lg)) for lg in free.logits] == free.output
    forced = srv.serve([Request(rid=1, prompt=prompt,
                                forced=free.output[:-1])])[0]
    assert forced.output == free.output
    np.testing.assert_array_equal(np.stack(forced.logits),
                                  np.stack(free.logits))


def test_compile_is_reused_by_dispatch():
    """``Server.compile`` builds the very executables ``serve`` dispatches:
    serving after it compiles nothing new."""
    from jax._src import monitoring
    cfg = get_smoke_config("minicpm_2b")
    par = ParallelConfig(tp=1, dp=1)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    srv = Server(cfg, par, mesh, M.init_model(jax.random.PRNGKey(1), cfg, par),
                 ServeConfig(max_batch=2, max_seq=64, eos_token=-1,
                             max_new_tokens=3, prefill_chunk=8))
    secs = srv.compile()
    assert set(secs) == {"decode", "chunk"}
    compiles = []

    def listen(event, secs, **kw):
        if "backend_compile" in event:
            compiles.append(event)
    monitoring.register_event_duration_secs_listener(listen)
    try:
        prompt = np.arange(11, dtype=np.int32) % cfg.vocab_size
        done = srv.serve([Request(rid=0, prompt=prompt)])
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert len(done[0].output) == 3
    assert compiles == []


def test_params_placed_with_pspecs(subproc):
    """At tp=4 the server holds its parameters (and KV pool) sharded as
    ``pspecs`` (``cache_specs``) say, whatever placement it was handed."""
    out = subproc("""
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ParallelConfig, get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.runtime.server import ServeConfig, Server

cfg = get_smoke_config("minicpm_2b")
par = ParallelConfig(tp=4, dp=1)
mesh = make_mesh(1, 1, 4)
params = M.init_model(jax.random.PRNGKey(0), cfg, par)
assert len(jax.tree.leaves(params)[0].sharding.device_set) == 1
srv = Server(cfg, par, mesh, params,
             ServeConfig(max_batch=2, max_seq=64, max_new_tokens=2))
for tree, specs in ((srv.params, srv.pspecs), (srv.caches, srv.cache_specs)):
    leaves, specs_flat = jax.tree.leaves(tree), jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(specs_flat)
    for x, sp in zip(leaves, specs_flat):
        assert x.sharding.is_equivalent_to(NamedSharding(mesh, sp), x.ndim), \\
            (x.shape, x.sharding, sp)
sharded = [x for x in jax.tree.leaves(srv.params)
           if not x.sharding.is_fully_replicated]
assert sharded and all(len(x.sharding.device_set) == 4 for x in sharded)
print("placed", len(sharded))
""")
    assert "placed" in out
