"""Model type ``tiny_moe``, for a test only: a mixture-of-experts decoder,
the registry's ``dbrx-132b`` at the sizes of its configuration file. It
gives only the program's configuration: the test that uses it builds,
warms up and serves the model, and compares nothing with a reference."""
import dataclasses


def program_config(conf: dict, base):
    hf = conf["hf_config"]
    return dataclasses.replace(
        base, n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        head_dim=hf["head_dim"], rope_theta=float(hf["rope_theta"]),
        norm_eps=hf["rms_norm_eps"], n_experts=hf["num_local_experts"],
        n_experts_per_tok=hf["num_experts_per_tok"])
