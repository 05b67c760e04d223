"""Share of the traced window the device spent in the flash attention
kernels of the **global** layers of a cell whose layers are of two kinds:
the ``tepdist_flash_*`` events whose name carries no window
(``_window_flash.py``'s ``NAME`` reads the ``__w<window>`` a call's own
name has or lacks). Beside ``attn_time_share.train``, which counts both
kinds: at a sequence many windows long the unwindowed kernels are most of
it, and a change to the window path moves one and not the other. A program
whose attention kernels all carry a window, or none, or that has no such
kernel, gives nothing to read."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _window_flash

NAME, UNIT, LAYER = "attn_global_time_share.train", "%", "kernels"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def read(trace, host, cell):
    windowed, unwindowed = 0.0, 0.0
    for text, seconds, _ in trace.ops(_window_flash.is_attention):
        window = _window_flash.NAME.search(
            trace_reduce.short_name(text)).group(4)
        if window:
            windowed += seconds
        else:
            unwindowed += seconds
    if windowed <= 0 or unwindowed <= 0:
        return None
    print(f"attention kernels: global layers {unwindowed:.6f} s, window "
          f"layers {windowed:.6f} s of a {trace.window_s:.6f} s window",
          flush=True)
    return 100.0 * unwindowed / trace.window_s
