"""The comparison that decides `correct` fails where it should: the
control (the reference one precision down) and each fault the timed path
can have, planted underneath a whole run of a tiny cell on the CPU."""
import jax
import jax.numpy as jnp
import pytest

SERVING = ["tiny.batch", "tiny.chat"]
KV = ["tiny.kv_skew", "tiny.kv_uniform"]


# ---------------------------------------------------------------- faults
def _serving_fault(kind):
    from repro.models.transformer import decode_step
    from repro.serving import engine

    def broken(params, cfg, cache, tokens, pos):
        logits, new = decode_step(params, cfg, cache, tokens, pos)
        if kind == "state_unchanged":
            return logits, cache
        if kind == "half_batch":    # only the second half computed
            B = logits.shape[0]
            return jnp.concatenate([logits[B // 2:], logits[B // 2:]])[:B], \
                new
        # a token altered where it is produced
        bump = jnp.where(pos % 3 == 1, 1e4, 0.0).astype(logits.dtype)
        return logits.at[:, 5].add(bump), new

    def apply(drv):
        engine._decode = jax.jit(broken, static_argnums=1)
    return apply


def _kv_fault(kind):
    def apply(drv):
        kv = drv.kv
        if kind == "state_unchanged":
            kv.write_page = lambda page, k, v: None
        elif kind == "tracker_unchanged":
            kv.tracker.record = lambda mask: None
        elif kind == "half_batch":
            orig = kv.read_pages
            kv.read_pages = lambda pages: orig(pages)[:len(pages) // 2]
        else:                               # an answer altered
            orig = kv.read_pages

            def altered(pages):
                got = orig(pages)
                got[0] = got[0].at[0, 0, 0, 0, 0].add(1)
                return got
            kv.read_pages = altered
    return apply


@pytest.mark.parametrize("name", SERVING)
@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_serving_fault_is_not_correct(run_tiny, monkeypatch, name, kind):
    from repro.serving import engine
    monkeypatch.setattr(engine, "_decode", engine._decode)
    r = run_tiny(name, seconds=0.3, after_setup=_serving_fault(kind))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name", KV)
@pytest.mark.parametrize("kind", ["state_unchanged", "tracker_unchanged",
                                  "half_batch", "answer_altered"])
def test_kv_fault_is_not_correct(run_tiny, name, kind):
    r = run_tiny(name, seconds=0.3, after_setup=_kv_fault(kind))
    assert not r["correct"], r["checks"]


# --------------------------------------------------------------- control
@pytest.mark.parametrize("name", SERVING + KV)
def test_control_is_not_correct(tiny_cell, name):
    """The reference one precision down (float8 weights or pages,
    bfloat16 scores) fails one of the cell's numbers, while the program
    on the same window passes them all."""
    from bench.lib.harness import compare, control_cell
    from bench.lib.cell import load_module
    cell = tiny_cell(name)
    drv = load_module("drivers", cell.traffic["driver"], cell.root).Driver
    limits = {k: cell.limits()[k] for k in drv.CHECKS}
    r = control_cell(cell, 21, 0.4, compile_cache=False)
    assert compare(r["program"], limits)[0]
    ctrl = {k: r["control"].get(k, 0) for k in limits}
    assert not compare(ctrl, limits)[0], r
