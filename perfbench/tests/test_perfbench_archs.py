"""The architecture modules: each configuration's count of parameters is
the program's, and the reference moved into ``archs/dense.py`` reads as
it did before the move."""
import jax
import pytest

import harness
import reference

CONFIGS = [c["name"] for c in harness.load_benchmark()["configs"]]


@pytest.mark.parametrize("config", CONFIGS)
def test_the_shapes_counted_are_the_programs_parameters(config):
    from repro.models import zoo
    cfg = harness.load_config(config)
    arch = harness.arch_module(cfg)
    cfg = arch.tiny(cfg)
    params = jax.eval_shape(lambda: zoo.init_params(
        arch.model_config(cfg), jax.random.PRNGKey(0)))
    program = {name: tuple(x.shape) for name, x in zip(
        harness.leaf_names(params), jax.tree_util.tree_leaves(params))}
    assert arch.param_shapes(cfg) == program


# yi-6b at the dense module's tiny size, seed 12345: the reference's
# three losses and first gradient norms as the harness's own reference
# computed them before the decoder moved out of it
PINNED_LOSSES = [6.281714677810669, 6.25911021232605, 6.238624572753906]
PINNED_GRAD1 = {
    "emb/embed": 0.6224645972251892,
    "emb/unembed": 0.6132142543792725,
    "final_norm/scale": 0.014869476668536663,
    "layers/attn/wk": 0.0035763676278293133,
    "layers/attn/wo": 0.4111150801181793,
    "layers/attn/wq": 0.0035433853045105934,
    "layers/attn/wv": 0.21640725433826447,
    "layers/ffn/w_down": 0.11642094701528549,
    "layers/ffn/w_gate": 0.058812979608774185,
    "layers/ffn/w_up": 0.05809865891933441,
    "layers/ln1/scale": 0.004562910180538893,
    "layers/ln2/scale": 0.0017295277211815119,
}


def test_the_moved_reference_reads_as_before():
    cfg = harness.load_config("yi-6b")
    arch = harness.arch_module(cfg)
    cfg = arch.tiny(cfg)
    seed, batch, seq = 12345, cfg["job"]["batch"], cfg["job"]["seq_len"]
    events = [reference.event_tokens(range(o, o + batch), seq,
                                     cfg["vocab_size"], seed)
              for o in range(0, 3 * batch, batch)]
    ref = reference.first_steps(
        cfg, seed, [(e[:, :-1], e[:, 1:]) for e in events], arch=arch)
    assert ref["losses"] == pytest.approx(PINNED_LOSSES, rel=1e-6)
    assert ref["grad1"] == pytest.approx(PINNED_GRAD1, rel=1e-6)
