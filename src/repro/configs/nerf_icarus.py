"""nerf-icarus — the paper's own workload: the original NeRF MLP run through
the ICARUS PLCore pipeline (PEU -> MLP engine -> VRU).

Original NeRF: 8x256 trunk, skip at layer 4, density head + 128-wide
view-dependent color branch; positional encoding L=10 (position) / L=4
(direction); ~1.19M params (paper: "around 1,200,000 parameters, 4.6MB").
Two-pass sampling: 64 uniform + 128 importance (paper §5.1: 192 samples).

``MIPNERF`` is Mip-NeRF (Barron et al., arXiv:2103.13415; ``google/mipnerf``
``configs/blender.gin``) on the same path: every sample is a conical
frustum of the ray's pixel footprint, encoded by its integrated positional
encoding (IPE), and ONE network serves both passes over 128 + 128
intervals.
"""
import dataclasses
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
    # nerf_fixed | rff_iso | rff_aniso | ipe (Mip-NeRF's integrated PE of
    # a frustum Gaussian: sin/cos of 2^l mean, each scaled by
    # exp(-4^l var / 2), l < pos_freqs, no identity; needs cone rays)
    encoding_mode: str = "nerf_fixed"
    rff_features: int = 128     # per Fig.4(b): 3x128 frequency-matrix memories
    rff_sigma: float = 10.0
    # sampling (paper §5.1 two-pass strategy)
    n_coarse: int = 64
    n_fine: int = 128
    near: float = 2.0
    far: float = 6.0
    # Mip-NeRF. "ray": point samples (NeRF). "cone": every ray carries its
    # pixel radius, samples are the n_coarse (then n_fine) intervals
    # between n + 1 edges, each cast to a frustum Gaussian, and the volume
    # integral runs over those finite intervals.
    ray_shape: str = "ray"
    # one network evaluated by both passes (params["coarse"]; a "fine"
    # entry is ignored): the kernel pins one weight set
    shared_net: bool = False
    # density = softplus(raw + density_bias) (Mip-NeRF) or relu(raw)
    density_activation: str = "relu"
    density_bias: float = 0.0
    # rgb = sigmoid(raw) * (1 + 2 p) - p
    rgb_padding: float = 0.0
    # "nerf": inverse CDF over the coarse points, fine set merged with the
    # coarse one. "mip": the coarse weights blurred (2-tap max, 2-tap
    # mean) plus resample_padding, n_fine + 1 new edges drawn from the
    # piecewise-constant PDF; the fine pass runs on those alone.
    resampler: str = "nerf"
    resample_padding: float = 0.0
    # RMCM quantization (paper §4.3)
    rmcm_bits: int = 9          # signed-magnitude: 1 sign + 8 magnitude bits
    rmcm_enabled: bool = True
    # render batching — PLCore analogue: rays per fused-kernel tile
    rays_per_tile: int = 128    # paper batch-computing: 128 samples weight-stationary
    # fused-kernel scoped-VMEM budget: the ray tile rt is the largest
    # whose VMEM model (kernels.ops.two_pass_vmem_bytes) fits it, and the
    # compiler is handed that model's figure as its scoped limit. Half of
    # a TPU v5e's 128 MiB of VMEM (its default scoped limit is 16 MiB),
    # the rest left to what the model does not count. The one-kernel
    # two-pass path pins BOTH networks' gathered weight stacks as the
    # working set every grid step plus one ray block's scratch (26.5 MiB
    # modelled at CONFIG's block of 4 rays, above the 16 MiB default);
    # the per-ray in/out blocks take the rest.
    # Mesh-sharding the weights shrinks the HBM-resident footprint, not
    # this working set.
    kernel_vmem_budget_mb: float = 64.0
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

    def __post_init__(self):
        if self.density_activation not in ("relu", "softplus"):
            raise ValueError(f"unknown density_activation "
                             f"{self.density_activation!r}")
        mip = (self.ray_shape == "cone", self.encoding_mode == "ipe",
               self.resampler == "mip", self.shared_net,
               self.density_activation == "softplus")
        if any(mip[:3]) and not all(mip):
            raise ValueError(
                "Mip-NeRF comes whole: ray_shape='cone', "
                "encoding_mode='ipe', resampler='mip', shared_net=True and "
                "density_activation='softplus' together, got "
                f"{self.ray_shape!r}, {self.encoding_mode!r}, "
                f"{self.resampler!r}, {self.shared_net!r}, "
                f"{self.density_activation!r}")
        if mip[0] and self.n_coarse != self.n_fine:
            raise ValueError("cone rays draw as many intervals at both "
                             f"levels: n_coarse {self.n_coarse} != "
                             f"n_fine {self.n_fine}")
        if not mip[0] and (self.shared_net
                           or self.density_activation != "relu"
                           or self.density_bias or self.rgb_padding
                           or self.resample_padding):
            raise ValueError("shared_net, density_activation, "
                             "density_bias, rgb_padding and "
                             "resample_padding are read by the cone path "
                             "only")

    @property
    def cone(self) -> bool:
        return self.ray_shape == "cone"

    @property
    def pos_enc_dim(self) -> int:
        if self.encoding_mode == "ipe":
            return 2 * 3 * self.pos_freqs     # sin/cos, no identity
        return 3 + 2 * 3 * self.pos_freqs     # identity + sin/cos

    @property
    def dir_enc_dim(self) -> int:
        return 3 + 2 * 3 * self.dir_freqs

    @property
    def n_samples(self) -> int:
        return self.n_coarse + self.n_fine


CONFIG = NerfConfig()


# Mip-NeRF at its published widths (configs/blender.gin, internal/models.py):
# 8x256 trunk with the input joined to the fifth layer's output (so layer 5
# reads [h | IPE]), 256 bottleneck, 1x128 colour branch, IPE over
# 2^0..2^15, direction PE L=4 with identity, 128 + 128 samples, white
# background (the render paths' default).
MIPNERF = NerfConfig(
    name="mipnerf", skip_at=(5,), pos_freqs=16, dir_freqs=4,
    encoding_mode="ipe", n_coarse=128, n_fine=128, ray_shape="cone",
    shared_net=True, density_activation="softplus", density_bias=-1.0,
    rgb_padding=0.001, resampler="mip", resample_padding=0.01)


def tiny_mip() -> NerfConfig:
    """``MIPNERF`` cut to size for CPU tests."""
    return dataclasses.replace(
        MIPNERF, trunk_layers=4, trunk_width=64, skip_at=(2,),
        color_width=32, pos_freqs=6, dir_freqs=3, n_coarse=16, n_fine=16,
        rays_per_tile=32, image_hw=(64, 64))


def tiny() -> NerfConfig:
    """Reduced config for CPU tests/examples."""
    return NerfConfig(
        trunk_layers=4, trunk_width=64, skip_at=(2,), color_width=32,
        pos_freqs=6, dir_freqs=3, n_coarse=16, n_fine=16,
        rays_per_tile=32, image_hw=(64, 64),
    )
