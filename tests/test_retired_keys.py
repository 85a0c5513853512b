"""Input that named an attention implementation or the pipeline layout: the
keys were retired in PR 57, and `options` is an open table, so each is
refused by name instead of being ignored."""

import pytest

from tpuserve.config import ModelConfig, load_config
from tpuserve.models import build

BERT = {"layers": 1, "d_model": 32, "heads": 2, "d_ff": 64, "vocab_size": 512}
GEN = {"layers": 1, "d_model": 32, "heads": 2, "prompt_len": 8,
       "max_new_tokens": 4, "vocab_size": 512}
SD = {"steps": 2, "vocab_size": 64, "text_layers": 1, "text_d_model": 16,
      "text_heads": 2, "unet_ch": 8, "unet_mults": [1, 2], "unet_res": 1,
      "unet_attn_levels": [0, 1], "unet_heads": 2, "vae_ch": 8,
      "vae_mults": [1, 2]}

# (what the configuration says, the key its refusal names, what decides now)
RETIRED = {
    "bert-attention-dense": (dict(family="bert", options={**BERT, "attention": "dense"}),
                             "options.attention", "attention_path"),
    "bert-attention-flash": (dict(family="bert", options={**BERT, "attention": "flash"}),
                             "options.attention", "attention_path"),
    "bert-attention-ring": (dict(family="bert", sp=2, options={**BERT, "attention": "ring"}),
                            "options.attention", "attention_path"),
    "bert-attention-ulysses": (dict(family="bert", sp=2,
                                    options={**BERT, "attention": "ulysses"}),
                               "options.attention", "attention_path"),
    "textgen-attention-dense": (dict(family="textgen", options={**GEN, "attention": "dense"}),
                                "options.attention", "einsum pair"),
    "textgen-attention-flash": (dict(family="textgen", options={**GEN, "attention": "flash"}),
                                "options.attention", "einsum pair"),
    "sd15-unet_attention-dense": (dict(family="sd15", image_size=32,
                                       options={**SD, "unet_attention": "dense"}),
                                  "options.unet_attention", "dot_product_attention"),
    "sd15-unet_attention-flash": (dict(family="sd15", image_size=32,
                                       options={**SD, "unet_attention": "flash"}),
                                  "options.unet_attention", "dot_product_attention"),
    "parallelism-pipeline": (dict(family="bert", parallelism="pipeline", options=BERT),
                             "parallelism = 'pipeline'", "'sharded', 'replica' or 'single'"),
    "bert-pp_micro": (dict(family="bert", options={**BERT, "pp_micro": 2}),
                      "options.pp_micro", "'sharded', 'replica' or 'single'"),
}


@pytest.mark.parametrize("case", sorted(RETIRED))
def test_a_retired_key_is_refused_by_name(case):
    over, key, decides = RETIRED[case]
    with pytest.raises(ValueError) as e:
        build(ModelConfig(**{"name": "m", "dtype": "float32",
                             "parallelism": "single", **over}))
    assert key in str(e.value) and "retired" in str(e.value)
    assert decides in str(e.value)


def test_a_model_table_with_pp_is_refused_by_name(tmp_path):
    """`pp` was a field, not an option: the loader's unknown-key rule names it."""
    path = tmp_path / "serve.toml"
    path.write_text('[[model]]\nname = "b"\nfamily = "bert"\npp = 4\n')
    with pytest.raises(ValueError, match=r"unknown ModelConfig keys: \['pp'\]"):
        load_config(str(path))
