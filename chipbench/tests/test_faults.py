"""The comparison that decides ``correct`` catches a broken timed path.

Each test drives a whole run of the filter cell at tiny size on the CPU
(the look for a chip skipped), with one fault planted in the program
underneath, and sees ``correct`` come out false.  Faults: a step that
returns its state unchanged; half of the batch left out, the mean taken
over the rest; a record altered where it is produced.  The cell runs on
one chip, so no exchange between chips exists to leave out.  A sound run
comes out true.
"""

import jax
import jax.numpy as jnp
import pytest

from chipbench import run as run_lib

FILTER = "rbpf.paper"


def _run(root, cell, seed=3):
    res = run_lib.run_cell(cell, seed, 2.0, False, root=root, require_chip=False,
                           log=lambda s: None)
    return res


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_sound_run_is_correct(tiny, seed):
    res = _run(tiny, FILTER, seed)
    assert res["correct"], res["checks"]


# -- the filter ---------------------------------------------------------------


def _patch_rbpf_step(monkeypatch, wrap):
    from repro.smc.programs import rbpf

    real_build = rbpf.build

    def build():
        ssm, params = real_build()
        step = ssm.step
        return ssm._replace(step=lambda *a: wrap(step, *a)), params

    monkeypatch.setattr(rbpf, "build", build)


def test_filter_state_unchanged(tiny, monkeypatch):
    def wrap(step, key, state, t, y, params):
        _, logw, record = step(key, state, t, y, params)
        return state, logw, record  # the particles never move

    _patch_rbpf_step(monkeypatch, wrap)
    assert not _run(tiny, FILTER)["correct"]


def test_filter_half_batch_left_out(tiny, monkeypatch):
    # The evidence increment averages the weights of half the particles.
    real = jax.scipy.special.logsumexp

    def half_logsumexp(a, *args, **kwargs):
        if getattr(a, "ndim", 0) == 1 and not args and not kwargs:
            return real(a[: a.shape[0] // 2]) + jnp.log(2.0)
        return real(a, *args, **kwargs)

    monkeypatch.setattr(jax.scipy.special, "logsumexp", half_logsumexp)
    assert not _run(tiny, FILTER)["correct"]


def test_filter_record_altered(tiny, monkeypatch):
    from repro.smc import filters

    real = filters.store_lib.append

    def append(cfg, store, values):
        return real(cfg, store, values.at[0, 1].add(0.5))

    monkeypatch.setattr(filters.store_lib, "append", append)
    assert not _run(tiny, FILTER)["correct"]
