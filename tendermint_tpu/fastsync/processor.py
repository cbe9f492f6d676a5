"""Processor: pure verify-and-apply queue.

Reference parity: blockchain/v2/processor.go:173 (pure state machine:
holds downloaded blocks, yields contiguous (first, second) pairs for
verification, tracks the verification rule "block N is proven by the
LastCommit inside block N+1" from blockchain/v0/reactor.go:216).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from ..crypto import batch as crypto_batch
from ..types import Block, BlockID, Commit


class Processor:
    def __init__(self, height: int):
        self.height = height  # next height to apply
        # height -> (block, peer, what the receive path measured on the way in)
        self.blocks: Dict[int, Tuple[Block, str, dict]] = {}

    def add_block(
        self, height: int, block: Block, peer_id: str, received: Optional[dict] = None
    ) -> None:
        self.blocks.setdefault(height, (block, peer_id, received or {}))

    def received(self, height: int) -> dict:
        """What the reactor noted as the block at `height` arrived (its
        bytes, decode and download times, the peer): fields of that block's
        `fastsync.block` span."""
        entry = self.blocks.get(height)
        return {} if entry is None else entry[2]

    def peek_two(self) -> Optional[Tuple[Block, Block]]:
        """The v0 trySync pair: block H and block H+1 (whose LastCommit
        proves H)."""
        first = self.blocks.get(self.height)
        second = self.blocks.get(self.height + 1)
        if first is None or second is None:
            return None
        return first[0], second[0]

    def pop_processed(self) -> None:
        self.blocks.pop(self.height, None)
        self.height += 1

    def drop_invalid(self) -> Tuple[int, ...]:
        """Both blocks of the failing pair are suspect (v0 pool
        RedoRequest): drops them and returns the dropped heights.  Peer
        attribution/punishment is the scheduler's job (it tracks who
        delivered each height in `received`)."""
        dropped = []
        for h in (self.height, self.height + 1):
            if self.blocks.pop(h, None) is not None:
                dropped.append(h)
        return tuple(dropped)

    def drop_heights(self, heights) -> None:
        """Forget blocks whose delivering peer was removed so the scheduler's
        re-request actually replaces them (otherwise add_block's setdefault
        would keep the stale copy)."""
        for h in heights:
            self.blocks.pop(h, None)

    def pending_range(self) -> int:
        return len(self.blocks)


def verify_commit_run(
    val_set, chain_id: str, pairs: Sequence[Tuple[BlockID, int, Commit]]
) -> List[bool]:
    """Batch-verify the commits of a RUN of heights that share one validator
    set in a single device call — the cross-height batching that makes the
    10k-validator replay config (BASELINE config #5) saturate the chip.

    pairs: (block_id, height, commit) per height.  Returns per-height ok.
    """
    from ..types.agg_commit import AggregateCommit

    idxs: List[Tuple[int, int, bool]] = []  # (pair_idx, sig_idx, vote is for the block)
    pubkeys, msgs, sigs = [], [], []
    set_keys = [v.pub_key for v in val_set.validators]
    structural_ok = []
    agg_items: List[Tuple[int, tuple]] = []  # (pair_idx, claim) — one batch
    agg_power: dict = {}
    for pi, (block_id, height, commit) in enumerate(pairs):
        try:
            if val_set.size() != commit.size():
                raise ValueError("commit size mismatch")
            commit.validate_basic()
            if height != commit.height or block_id != commit.block_id:
                raise ValueError("wrong height/block id")
        except ValueError:
            structural_ok.append(False)
            continue
        structural_ok.append(True)
        if isinstance(commit, AggregateCommit):
            # the whole run of aggregate commits becomes ONE blinded
            # pairing product below (k commits, one final exponentiation)
            signer_idxs = commit.signers.true_indices()
            try:
                pks = [val_set.validators[i].pub_key.bytes() for i in signer_idxs]
            except IndexError:
                structural_ok[pi] = False
                continue
            agg_items.append((pi, (pks, commit.sign_message(chain_id), commit.agg_sig)))
            agg_power[pi] = sum(val_set.validators[i].voting_power for i in signer_idxs)
            continue
        batch = commit.vote_batch(chain_id, set_keys)
        idxs.extend((pi, i, counts) for i, counts in zip(batch.idxs, batch.for_block))
        pubkeys.extend(batch.pub_keys)
        msgs.extend(batch.msgs)
        sigs.extend(batch.sigs)

    # type-routed: ed25519 rides the batch engine, other key types verify
    # via their own PubKey.verify (same dispatch as ValidatorSet.verify_commit)
    from ..types.validator import mixed_batch_verify

    ok = mixed_batch_verify(pubkeys, msgs, sigs)

    tallied = [0] * len(pairs)
    sig_ok = [True] * len(pairs)
    needed = val_set.total_voting_power() * 2 // 3
    for (pi, i, counts), good in zip(idxs, ok):
        if not good:
            sig_ok[pi] = False
        elif counts:
            tallied[pi] += val_set.validators[i].voting_power
    if agg_items:
        from ..crypto.bls import scheme as _bls_scheme

        agg_ok = _bls_scheme.batch_verify_aggregates([c for _, c in agg_items])
        for (pi, _), good in zip(agg_items, agg_ok):
            if not good:
                sig_ok[pi] = False
            else:
                tallied[pi] = agg_power[pi]
    return [
        structural_ok[pi] and sig_ok[pi] and tallied[pi] > needed for pi in range(len(pairs))
    ]
