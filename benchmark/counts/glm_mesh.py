"""Required work of one IRLS iteration of a dense GLM on a mesh: counts/glm.py
`step`, the same function. Over all rows it is the whole mesh's work
(`fit_step_mfu_pct` sets it against chips x peak); `irls_shard_roofline`
hands it one chip's rows."""

import manifest

step = manifest.load_module("counts", "glm").step
