# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# Timing policy (noisy shared host): every timed region is closed by
# jax.block_until_ready (async dispatch otherwise measures queue depth,
# not work); per-module estimators are median-of-N samples, except the
# fused-vs-unfused gate which keeps interleaved min-of-N (and reports the
# median alongside in the derived column).
import sys


def main() -> None:
    sys.path.insert(0, "src")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import table1_kernels, table23_array, fig8_sizes, \
        tpu_matmul, roofline_report, fused_epilogue, int8_decode

    print("name,us_per_call,derived")
    for mod in (table1_kernels, table23_array, fig8_sizes, tpu_matmul,
                roofline_report, fused_epilogue, int8_decode):
        for name, us, derived in mod.rows():
            print(f"{name},{us:.2f},{derived}")


if __name__ == '__main__':
    main()
