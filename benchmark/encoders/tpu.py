"""The encoder a configuration names as "tpu": the program's device
encoder, taken by name (never the ``auto`` resolution), with the compile
cache where ``runtime/jaxcache.py`` puts it. ``make`` fails where what
it got is not the device's. In the CPU rehearsal the same class runs on
the CPU platform in interpret mode, as ``chip_smoke.py --dry-run-cpu``
does."""


def make(rehearse_cpu: bool, say):
    if rehearse_cpu:
        from lizardfs_tpu.core.encoder import TpuChunkEncoder

        return TpuChunkEncoder(force_cpu=True, interpret=True)
    from lizardfs_tpu.core.encoder import get_encoder
    from lizardfs_tpu.runtime.jaxcache import configure_compile_cache

    say(f"compile cache: {configure_compile_cache()}")
    enc = get_encoder("tpu")
    if enc.name != "tpu" or enc.device.platform != "tpu":
        raise SystemExit(f"FAIL: encoder resolved to {enc.name} on "
                         f"{enc.device}")
    return enc
