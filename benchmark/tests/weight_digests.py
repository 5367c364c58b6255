"""A digest of every leaf a family's ``make_params`` draws, at the family's
rehearsal size, for each weight type, with and without the reciting head, on
a one-device and a four-device CPU mesh: printed as one JSON object.

``recorded_weights.json`` is what PR 26's parent (``lib/serve.py
make_llama_params``) printed for ``mistral``; ``test_families.py`` runs this
file in a process of its own (the device count is fixed before JAX loads)
and holds the family to it. A cell's numbers are properties of one weight
draw (PERF.md section 4), so a family whose leaves move has redrawn every
cell that serves it.

    python3 benchmark/tests/weight_digests.py <a configuration file of the family>
"""

import hashlib
import json
import os
import sys

SEED = 2**31 + 11  # over 32 signed bits, as the configurations' weights_seed is


def main(config_path: str) -> dict:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    import jax
    import numpy as np
    from flax import traverse_util

    from benchmark.lib import serve
    from rag_llm_k8s_tpu.core.config import DTypePolicy, MeshConfig
    from rag_llm_k8s_tpu.core.mesh import make_mesh

    cfg, family = serve.load_config(config_path)
    cfg.update(family.REHEARSAL_MODEL)
    model = family.model_config(cfg)
    out = {}
    for tp in (1, 4):
        mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=tp), devices=jax.devices()[:tp])
        for quant in ("bf16", "int8"):
            for gain in (0.0, 5.0):
                params = family.make_params(model, DTypePolicy(), SEED, quant, mesh, gain)
                out[f"tp{tp}.{quant}.recite{gain:g}"] = {
                    "/".join(path): hashlib.sha256(
                        (str(a.dtype) + str(a.shape)).encode() + np.asarray(a).tobytes()).hexdigest()[:16]
                    for path, a in sorted(traverse_util.flatten_dict(params).items())}
    return out


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1]), sort_keys=True))
