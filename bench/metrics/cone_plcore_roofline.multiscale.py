"""Fused cone kernel (Mip-NeRF's ``plcore_two_pass_cone``): share of its
roofline on the device trace: the nominal FLOP and bytes of its calls,
each a tile of ``tile_rays`` rays, over their device time. ``None``
where the trace holds no such kernel."""
from bench import mip_flops, roofline

KERNEL = "%plcore_two_pass_cone"


def read(run):
    t = run.trace
    if t is None or not run.peak or not sum(t.kernel_calls.values()):
        return None
    if not any(name.startswith(KERNEL) for name, _ in t.device_ops):
        return None
    calls = sum(t.kernel_calls.values())
    share, _ = roofline.roofline_share(
        calls * mip_flops.flops_per_call(run.arch, run.tile_rays),
        calls * mip_flops.kernel_bytes(run.arch, run.tile_rays),
        sum(t.kernel_s.values()), run.peak)
    return share
