"""Model FLOPs of the finished prompts at the bf16 peak over the sum of
their TTFTs, %: the whole prefill step's share of the chip."""
from chipbench.readers import mfu_ttft


def read(run):
    return mfu_ttft(run)
