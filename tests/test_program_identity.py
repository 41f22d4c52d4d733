"""The paged engine's programs for the model classes of the accepted serve
cells come out as pinned (``tools/program_identity.py``: StableHLO with
locations stripped, kernels in interpret mode). The pins are the programs
of PR 30, which PR 31 (a third class of cache beside them) had to leave as
they were, as PR 33 did (a looped model's two programs are pinned beside
them), and PR 37 (``DeepseekV3Attention`` takes its sizes as arguments:
kanana's two programs are byte for byte what they were; the new model's two
are pinned beside them; PR 38 changed those two: the selection kernel; PR 39 pins MiMo's two, which its generalized attention
class left as they were, and Laguna's; LFM2's two beside them). The three
models whose cells hold
a share of their experts are fingerprinted holding a share; kanana's two
programs run the expert kernel. The five MoE models' decode blocks are also
pinned at ``moe.FEW_ROWS`` slots, the most rows a decode form of the
routed experts takes, as they were before a prefill chunk had a kernel of
its own. A change that means to alter one of them updates its pin here
and says so in CHANGES.md; one that does not has changed what
``mistral7b-decode-sat``, ``kanana2-docqa-decode``, ``ouro-reason-decode``,
``mimo-v2-agent-decode`` or ``dots3-longdoc-decode`` runs."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Every block program whose walk has no window reads its table through run
# copies (an aligned group of 8 columns naming consecutive blocks moves as
# one copy: a third scalar-prefetch operand); dots3_note's, whose full
# layers read through the indexer and the selected rows, is as it was.
PINNED = {
    "llama.block": "d34c8f852a642a4b67d68eae31cd994a9ad17709",
    "llama.chunk": "db442fc2d52832c9bed72a9837dd5b2c6cbddd0f",
    "llama_int8.block": "6baa4810a40bc6eb98cf2398f9f9d475972ffe83",
    "llama_int8.chunk": "315454823dde9af0dd1aac50adea43a61726c18c",
    # a decode step (and the tiny chunk, 8 rows) on all the experts runs
    # the kernel moe_hit_experts_decode in place of the grouped matmuls
    "deepseek_v3.block": "5db61e2f8fc4458d071abba278c88aec575dd66c",
    "deepseek_v3.chunk": "345ec2f7a03d92158340f21cbde341a27726c183",
    # PR 33: a looped model's two programs (the passes a scan in each)
    "ouro.block": "10e6816b507cd95d3dbb8ba716469a2b7872dab2",
    "ouro.chunk": "07b38559f2adab2faa4491cf7dcc852151f6073c",
    # PR 37: learned sparse attention over a latent cache through the hybrid
    # backend (the kernels' bodies are in the text); PR 38: the selection
    # is the kernel dsa_select_topk, and the counters two columns wider
    # this model and the next two hold a SHARE of their experts, as their
    # cells do: the same six programs as before the expert kernel
    "dots3_note.block": "c7892f7fd9fa0f0d62ac24c54e3ced43497920f3",
    "dots3_note.chunk": "1f0f71c0880f7d343abbec810cfa90398a7fb606",
    # PR 39: MiMo's two programs as the parent commit made them, before
    # MiMoV2Attention took its sizes per kind as arguments (query heads,
    # RoPE rule, sink, gate, value scale); Laguna's two beside them
    "mimo_v2.block": "b5666d0a8ca000e42ee670a1c4b6dc9a94c1199d",
    "mimo_v2.chunk": "bcd4088c52475b3c2faefc7d0be78b9f98d8248c",
    "laguna.block": "90924f5b8b30359e2e337d6055f37a88d1d357fa",
    "laguna.chunk": "8c8ec8c793d0ea5b798dfdd5ac9da1c340e49c47",
    # LFM2's two (a conv state under the block ids beside packed
    # arenas, all experts held); the fourteen above are as they were with
    # the conv leaf, the short-conv mixer and the engine's counters beside
    # them
    "lfm2_moe.block": "b145cc3fb9e276d2ce858b4a7c6ee6d380ec59d4",
    "lfm2_moe.chunk": "9f70bbacd5046938c7aa61f4bbd804e9d12e48a2",
    # each MoE model's decode block at moe.FEW_ROWS slots, the most rows a
    # decode form of its experts takes, as the parent of the chunk's
    # expert kernel (moe_grouped_experts_prefill, past FEW_ROWS rows) made
    # them: a chunk's kernel leaves every decode block as it was
    "deepseek_v3.block_few_rows": "2ddb05ba274052c9d9acf27fa3eea65756e52bb3",
    "dots3_note.block_few_rows": "aab01ac6ffd4d65c65a548b42af6d7908386c8ed",
    "mimo_v2.block_few_rows": "bd2abd533846e0f34be3976d756872b31579da5a",
    "laguna.block_few_rows": "5c3579ce07e48db233dbb9610f30e085bac81482",
    "lfm2_moe.block_few_rows": "9d9de179c2eac8e4e75cfbea0e78e0a100de937e",
}


@pytest.fixture(scope="module")
def fingerprints():
    # a process of its own: what an earlier test traced never shapes the text
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "program_identity.py")],
        capture_output=True, text=True, timeout=600, check=True)
    return json.loads(out.stdout)


@pytest.mark.parametrize("program", sorted(PINNED))
def test_accepted_cells_programs_are_as_pinned(fingerprints, program):
    assert fingerprints[program] == PINNED[program]
