"""The program's named scopes read back from compiled HLO and a trace,
on the CPU and without a chip."""

import benchpaths  # noqa: F401  (first: puts the harness on the path)

import json
import os

import pytest

from harness import scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _fixture():
    with open(os.path.join(HERE, "fixtures", "scopes_small.json")) as fh:
        d = json.load(fh)
    return "\n".join(d["hlo"]), trace.reduce(d["records"])


def test_scope_seconds_on_recorded_fixture():
    hlo, s = _fixture()
    names = scopes.op_names(hlo)
    assert names["copy.2"] == ""                  # no metadata
    assert "fusion.11" not in names               # not in the program
    # a fusion whose root lost its metadata (a scatter): by the op_name
    # nearest the root inside its fused computation
    assert scopes.in_scope(names["fusion.13"], scopes.CKPT_WRITE)
    # self time: the backward's loop less the ops it holds (200 ns);
    # a fusion by its root's op_name; a Pallas call by its instruction
    assert scopes.scope_seconds(s.op_s, names, scopes.ACA_BACKWARD) \
        == pytest.approx((200 + 200 + 100 + 100) * 1e-9)
    # overlapping scopes: the field VJP of the backward counts in both
    assert scopes.scope_seconds(s.op_s, names, scopes.FIELD) \
        == pytest.approx((200 + 200) * 1e-9)
    assert scopes.scope_seconds(s.op_s, names, scopes.CKPT_WRITE) \
        == pytest.approx((100 + 30) * 1e-9)
    assert scopes.unattributed(s.op_s, names) == {
        "copy.2": pytest.approx(100e-9), "fusion.11": pytest.approx(40e-9)}
    # each op once, the rk_stage kernel among them
    calls = {"body.5": "rk_stage"}
    assert scopes.covered_seconds(s.op_s, names, calls) \
        == pytest.approx(830e-9)
    assert [scopes.layers_of(op, names, calls) for op in (
        "fusion.7", "body.5[tpu_custom_call]", "copy.2", "fusion.11",
        "fusion.12")] == [
        "ode_field+ode_aca_backward", "ode_aca_backward+rk_stage",
        "no op_name", "not found",
        "other: jit(step)/jvp()/my_ode_field_norm/add"]

    ctx = {"summary": s, "scopes": names}
    assert s.busy_s[0] == pytest.approx(980e-9)
    assert scopes.share(ctx, scopes.FIELD) == pytest.approx(100 * 400 / 980)
    assert scopes.share(ctx, scopes.ACA_BACKWARD) \
        == pytest.approx(100 * 600 / 980)
    assert scopes.share(ctx, scopes.CKPT_WRITE) \
        == pytest.approx(100 * 130 / 980)
    # a program that carries no such scope reads nothing, not 0
    assert scopes.share(ctx, "ode_other") is None


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp()/while/body/ode_ckpt_write/select_n", True),
    ("jit(f)/transpose(jvp(ode_aca_backward))/while/body/"
     "transpose(jvp(vmap(ode_ckpt_write)))/add_any", True),
    ("ode_ckpt_write", True),
    ("jit(step)/my_ode_ckpt_write/add", False),
    ("jit(step)/ode_ckpt_writes/add", False),
    ("", False),
    (None, False),
])
def test_scope_is_a_whole_path_component(op_name, want):
    assert scopes.in_scope(op_name, scopes.CKPT_WRITE) is want


def test_names_two_programs_disagree_on_are_unknown():
    merged = scopes.merge([{"fusion.1": "a/ode_field/x", "copy.1": ""},
                           {"fusion.1": "b/y", "copy.1": ""}])
    assert merged == {"fusion.1": None, "copy.1": ""}


def test_window_programs_are_read_once_into_the_context():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope(scopes.FIELD):
            y = jnp.sin(x) * 2.0
        return y + 1.0

    compiled = jax.jit(f).lower(jnp.ones(8)).compile()
    _, s = _fixture()
    logged = []
    ctx = {"summary": s, "kernels": {}}
    names = scopes.window_op_names(ctx, log=logged.append)
    assert ctx["scopes"] is names
    assert any(scopes.in_scope(n, scopes.FIELD) for n in names.values())
    assert len(logged) == 1 and "of busy time" in logged[0]
    assert scopes.window_op_names(ctx, log=logged.append) is names
    assert len(logged) == 1
    del compiled
