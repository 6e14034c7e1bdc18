"""Pallas TPU kernels for the three compute hot-spots (DESIGN.md §11).

Three kernels, each with a pure-jnp oracle in `ref.py` that defines its exact
semantics (tests/test_kernels.py sweeps shapes against it):

* `gaussian_nbody` — tiled exact attraction sums u(t_i) = sum_j w_j K(t_i,s_j)
  with a flash-attention-style schedule (core/direct.py's `attraction`).
* `m2l_pair` — the separable M2L series of the FMM Taylor tier
  (core/expansions.py's `box_mass_taylor_log` inner product), pair axis on
  sublanes, mode products unrolled as lane-slice FMAs.
* `msp_update` — the fused phase-1 neuron update (membrane decay + spike draw
  + refractory + calcium) of core/msp.py's `step_neurons`, one HBM read +
  write per array instead of 6+ round-trips on the 500k-step loop.

Dispatch contract (`ops.py`): every wrapper takes `use_pallas` —

    None  (auto)  -> Pallas on TPU, the `ref.py` reference on CPU;
    True  (force) -> Pallas; on CPU this sets `interpret=True`, running the
                     kernel body in Python per grid step, so CPU CI can gate
                     parity against the oracle;
    False (off)   -> the reference, everywhere.

Any backend other than TPU and CPU raises instead of falling back.

Engine plumbing maps `EngineConfig.backend` ("reference"/"pallas"/"auto")
onto this flag via `ops.use_pallas_flag`; core modules import `ops` lazily so
the reference path never touches Pallas machinery.
"""

