"""Model FLOP/s utilisation of the Qwen3-Next cell: tokens/s/chip (untraced
part of the window) times `peaks_qwen3next.train_flops_per_token`, with the
held-expert term from the assignments the window's steps really made, over the
chip's bf16 peak."""
from benchmarks import peaks, peaks_qwen3next
from benchmarks.layer_metrics._qwen3next import is_qwen3next, moe_counter


def read(facts):
    rate = (facts.get("end_to_end") or {}).get("train_tok_s_chip")
    held = moe_counter(facts, "held_assignments_per_token")
    if rate is None or held is None or not is_qwen3next(facts) \
            or facts["device"]["platform"] != "tpu":
        return None         # a utilisation of the chip's peak, or nothing
    per_token = peaks_qwen3next.train_flops_per_token(
        facts["config"], int(facts["traffic"]["seq"]), float(held))
    peak = peaks.peaks_for(facts["device"]["kind"])["flops_per_s"]
    return 100.0 * rate * per_token / peak
