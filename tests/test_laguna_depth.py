"""The cut in depth is nothing but a cut: at toy size the whole 40-layer
pattern of layer types served through the engine agrees with the plain
reference as the five layers of the benchmark's configuration do."""
from paddle_tpu.models.laguna import LagunaConfig, init_params

from laguna_tiny import against_reference, engine, requests, tiny_model


def test_forty_layers_agree_with_the_reference_as_five_do():
    m = tiny_model(40)
    assert m["layer_types"].count("full_attention") == 10
    c = LagunaConfig.from_dict(m)
    params = init_params(c, seed=2)
    eng = engine(m, params, max_seqs=2, num_pages={"full": 33, "window": 20})
    reqs = requests([(21, 14), (6, 22)])
    for r in reqs:
        eng.submit(r)
    eng.run_pipelined()
    for r in reqs:
        first, lp = against_reference(m, params, r)
        assert first == 1.0 and lp < 5e-4, (r.rid, first, lp)
    # the first five entries of whole lists are the five-layer model
    five = LagunaConfig.from_dict(dict(m, num_hidden_layers=5))
    assert five == LagunaConfig.from_dict(tiny_model(5))
