import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, time
from repro.configs import reduced_config
from repro.configs.shapes import ShapeSpec
from repro.launch.steps import build_step
from repro.launch import analysis
from repro.parallel import meshctx
from repro.launch.mesh import make_mesh
from repro.kernels import ops as kops

mesh = make_mesh((2, 4), ("data", "model"))
for arch in ["granite-moe-1b-a400m", "jamba-v0.1-52b", "mamba2-370m", "whisper-base", "internvl2-2b"]:
    cfg = reduced_config(arch)
    if cfg.moe is not None:
        import dataclasses
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, impl="fse_dp"))
    for kind, shape in [("train", ShapeSpec("t", 64, 8, "train")),
                        ("prefill", ShapeSpec("p", 64, 8, "prefill")),
                        ("decode", ShapeSpec("d", 64, 8, "decode"))]:
        t0 = time.time()
        with meshctx.with_mesh(mesh), kops.use_kernels(False):
            fn, in_sh, out_sh, structs = build_step(cfg, shape, mesh)
            lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*structs)
            compiled = lowered.compile()
        cost = analysis.cost_dict(compiled)
        coll = analysis.collective_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
        print(f"{arch:24s} {kind:8s} ok {time.time()-t0:5.1f}s flops={cost.get('flops',0):.2e} "
              f"bytes={cost.get('bytes accessed',0):.2e} coll={coll['total']:.2e} "
              f"arg={getattr(mem,'argument_size_in_bytes',None)} temp={getattr(mem,'temp_size_in_bytes',None)}")
