"""Serving traffic: a closed loop of clients, each in a multi-turn session.

Every client waits for its reply before it sends its next turn (think time
0).  A turn's prompt is the shared system prompt, the session's whole
history — earlier user messages AND the tokens the engine generated — and a
new user message.  A session ends when its next turn would pass
``session_token_limit`` tokens; a fresh one starts at once.

User-message lengths and output budgets are quantile grids dealt in a fixed
schedule (``harness.grids.Deal``) to as many hands as there are clients.  The
run's seed decides which client plays which hand and draws every token id; it
does not touch a length.  With greedy decoding and no EOS every turn runs its
whole budget, so the lengths of every prompt and reply — and with them the
engine's whole schedule of ticks — are the same for every seed, and a
client's turns do not depend on how fast the engine answered.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from harness import grids


@dataclasses.dataclass
class Turn:
    prompt: np.ndarray        # int32 [prompt_len]
    max_new_tokens: int
    turn_index: int           # 0 for a session's first turn


class _Client:
    def __init__(self, params: dict, rng: np.random.Generator, hand: int,
                 vocab_size: int, system_prompt: np.ndarray,
                 messages: grids.Deal, budgets: grids.Deal):
        self._p = params
        self._rng = rng
        self._hand = hand
        self._vocab = vocab_size
        self._system = system_prompt
        self._messages, self._budgets = messages, budgets
        self._context = system_prompt
        self._turns = 0          # in this session
        self._dealt = 0          # over all sessions

    def next_turn(self, reply: Optional[List[int]]) -> Turn:
        """The turn after ``reply`` (None at the very start)."""
        if reply is not None:
            self._context = np.concatenate(
                [self._context, np.asarray(reply, np.int32)])
        message = self._rng.integers(
            0, self._vocab, self._messages.value(self._hand, self._dealt),
            dtype=np.int32)
        budget = self._budgets.value(self._hand, self._dealt)
        self._dealt += 1
        if (len(self._context) + len(message) + budget
                > self._p["session_token_limit"]):
            self._context, self._turns = self._system, 0
        self._context = np.concatenate([self._context, message])
        turn = Turn(self._context, budget, self._turns)
        self._turns += 1
        return turn


class Traffic:
    def __init__(self, params: dict, seed: int, vocab_size: int):
        n = params["clients"]
        rng = np.random.default_rng((seed, n))
        system = rng.integers(0, vocab_size, params["system_prompt_tokens"],
                              dtype=np.int32)
        hands = rng.permutation(n)
        messages = grids.Deal(
            grids.quantile_grid(params["user_message_tokens"]), n, 0)
        budgets = grids.Deal(
            grids.quantile_grid(params["output_tokens"]), n, 1)
        self.clients = [
            _Client(params, np.random.default_rng((seed, i)), int(hands[i]),
                    vocab_size, system, messages, budgets)
            for i in range(n)]


def make(params: dict, seed: int, vocab_size: int) -> Traffic:
    return Traffic(params, seed, vocab_size)
