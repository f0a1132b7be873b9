"""nerf-icarus — the paper's own workload: the original NeRF MLP run through
the ICARUS PLCore pipeline (PEU -> MLP engine -> VRU).

Original NeRF: 8x256 trunk, skip at layer 4, density head + 128-wide
view-dependent color branch; positional encoding L=10 (position) / L=4
(direction); ~1.19M params (paper: "around 1,200,000 parameters, 4.6MB").
Two-pass sampling: 64 uniform + 128 importance (paper §5.1: 192 samples).
"""
from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class NerfConfig:
    name: str = "nerf-icarus"
    # MLP engine
    trunk_layers: int = 8
    trunk_width: int = 256
    skip_at: Tuple[int, ...] = (4,)
    color_width: int = 128
    # PEU
    pos_freqs: int = 10         # L=10 -> 3 + 60 dims
    dir_freqs: int = 4          # L=4  -> 3 + 24 dims
    encoding_mode: str = "nerf_fixed"   # nerf_fixed | rff_iso | rff_aniso
    rff_features: int = 128     # per Fig.4(b): 3x128 frequency-matrix memories
    rff_sigma: float = 10.0
    # sampling (paper §5.1 two-pass strategy)
    n_coarse: int = 64
    n_fine: int = 128
    near: float = 2.0
    far: float = 6.0
    # RMCM quantization (paper §4.3)
    rmcm_bits: int = 9          # signed-magnitude: 1 sign + 8 magnitude bits
    rmcm_enabled: bool = True
    # render batching — PLCore analogue: rays per fused-kernel tile
    rays_per_tile: int = 128    # paper batch-computing: 128 samples weight-stationary
    # fused-kernel scoped-VMEM budget: the limit handed to the compiler
    # (16 MiB is its default scoped limit on TPU v5e, of 128 MiB of VMEM).
    # The one-kernel two-pass path pins BOTH networks' gathered weight
    # stacks as the working set every grid step (see
    # kernels.ops.two_pass_vmem_bytes) plus one ray block's scratch; the
    # ray tile rt is sized so the per-ray in/out blocks fit the rest.
    # Mesh-sharding the weights shrinks the HBM-resident footprint, not
    # this working set.
    kernel_vmem_budget_mb: float = 16.0
    # how the Pallas kernels run: None compiles them through Mosaic when
    # JAX's first device is a TPU and interprets them elsewhere; False
    # always compiles (a run that must be on the chip then fails off it
    # instead of interpreting); True always interprets.
    kernel_interpret: Optional[bool] = None
    # early ray termination (Cicero-style): after the coarse pass, rays whose
    # remaining transmittance T < ert_eps skip the fine-pass MLP and keep the
    # coarse color. 0.0 disables (exact two-pass render).
    ert_eps: float = 0.0
    image_hw: Tuple[int, int] = (800, 800)
    dtype: str = "float32"
    # §Perf lever: MLP-engine activation dtype. The VRU always integrates
    # in f32 (transmittance products underflow in bf16); bf16 halves the
    # dominant memory-roofline term of the render.
    compute_dtype: str = "float32"

    @property
    def pos_enc_dim(self) -> int:
        return 3 + 2 * 3 * self.pos_freqs     # identity + sin/cos

    @property
    def dir_enc_dim(self) -> int:
        return 3 + 2 * 3 * self.dir_freqs

    @property
    def n_samples(self) -> int:
        return self.n_coarse + self.n_fine


CONFIG = NerfConfig()


def tiny() -> NerfConfig:
    """Reduced config for CPU tests/examples."""
    return NerfConfig(
        trunk_layers=4, trunk_width=64, skip_at=(2,), color_width=32,
        pos_freqs=6, dir_freqs=3, n_coarse=16, n_fine=16,
        rays_per_tile=32, image_hw=(64, 64),
    )
