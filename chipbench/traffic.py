"""The one general traffic generator.

A deployment (chipbench/configs/<name>.json) says what data exists and
what one transfer event looks like; a traffic mix
(chipbench/traffic/<name>.json) says how many sessions send how wide a
request, and which requests of a session are reads (its `reads` block:
every n-th request a `lookup_accounts` as wide as the wire admits, its
ids drawn by the configuration's own key skew). Everything is a pure function of (seed,
stream, request index): a session's k-th request has the same bytes in
every run of one seed, however fast the server answers, and every seed
sends the same sizes. Bodies are built as numpy records (wire.py), never through
the program's packers.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import wire
from .zipfian import draw, zipfian_cdf

ACCOUNT_FLAGS = {"debits_must_not_exceed_credits": 1 << 1,
                 "credits_must_not_exceed_debits": 1 << 2}
F_PENDING, F_POST, F_VOID = 1 << 1, 1 << 2, 1 << 3
# Stream tags (low 16 bits of every transfer id's high limb): sessions
# count from 0, set-up streams sit above any session count.
STREAM_FUNDING = 0x7F00
STREAM_WARM = 0x7000
STREAM_PRELOAD = 0x7800
SEED_MASK = (1 << 63) - 1


@dataclasses.dataclass
class Request:
    operation: str      # the program's Operation member name
    payload: bytes      # n events of `event_size` bytes, no trailer
    n_events: int
    ids: np.ndarray     # (n, 2) u64: id_lo, id_hi of each event
    event_size: int = wire.TRANSFER.itemsize
    result: np.dtype = wire.RESULT  # one record of the reply; its itemsize
    #                                 is the result size on the wire

    @property
    def is_read(self) -> bool:
        return self.operation.startswith("lookup_")


def _rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng([seed & SEED_MASK, *path])


class Deployment:
    """Accounts and the shape of one event, from a configuration file."""

    def __init__(self, config: dict, seed: int, accounts_cut: int | None = None):
        acc = config["accounts"]
        self.config = config
        self.seed = seed
        self.n = accounts_cut or acc["count"]  # the population traffic draws from
        self.ledger = acc["ledger"]
        rng = _rng(seed, 0xACC)
        base = int(rng.integers(1, 1 << 47)) | 1
        i = np.arange(self.n, dtype=np.uint64)
        # 128-bit ids with both limbs in play.
        self.id_lo = np.uint64(base) + np.uint64(7) * i
        self.id_hi = i % np.uint64(5)
        every = acc.get("limited_every", 0)
        self.limited = ((i % np.uint64(every)) == 0) if every else \
            np.zeros(self.n, dtype=bool)
        self.limit_flag = ACCOUNT_FLAGS[acc["limit_flag"]] if every else 0
        self.funding_amount = acc.get("funding_amount", 0)
        tr = config["transfers"]
        self.cdf = zipfian_cdf(self.n, tr["key_skew"]["theta"])
        self.amount_lo, self.amount_hi = tr["amount"]
        fs = tr["fail_share"]
        self.fail_edges = np.cumsum([fs["same_account"],
                                     fs["unknown_account"],
                                     fs["wrong_ledger"]])
        self.unknown_share = fs["unknown_account"]
        self.two_phase = tr.get("two_phase")
        self.id_tag = int(rng.integers(1, 1 << 31)) << 16

    def account_ids(self) -> list[int]:
        return [(int(h) << 64) | int(l)
                for l, h in zip(self.id_lo, self.id_hi)]

    def account_requests(self, n_max: int) -> list[Request]:
        rec = np.zeros(self.n, dtype=wire.ACCOUNT)
        rec["id_lo"], rec["id_hi"] = self.id_lo, self.id_hi
        rec["ud64"] = np.arange(self.n)
        rec["ledger"] = self.ledger
        rec["code"] = self.config["accounts"]["code"]
        rec["flags"] = np.where(self.limited, self.limit_flag, 0)
        return [self._request("create_accounts", rec[i:i + n_max])
                for i in range(0, self.n, n_max)]

    def funding_requests(self, n_max: int) -> list[Request]:
        """One credit of `funding_amount` to every limited account, from
        the unlimited ones in turn."""
        lim = np.flatnonzero(self.limited)
        if not len(lim):
            return []
        plain = np.flatnonzero(~self.limited)
        src = plain[np.arange(len(lim)) % len(plain)]
        rec = self._blank(len(lim), STREAM_FUNDING, 0)
        rec["debit_lo"], rec["debit_hi"] = self.id_lo[src], self.id_hi[src]
        rec["credit_lo"], rec["credit_hi"] = self.id_lo[lim], self.id_hi[lim]
        rec["amount_lo"] = self.funding_amount
        step = min(n_max, self.config["accounts"]["funding_events_per_request"])
        return [self._request("create_transfers", rec[i:i + step])
                for i in range(0, len(rec), step)]

    def preload_widths(self, count: int, n_max: int) -> list[int]:
        """Events in each request of the state that exists before the
        window: `count` transfers in wire-max requests, the last one
        holding what is left. Request k of them is
        `transfer_request(STREAM_PRELOAD, k, width)`: the deployment's
        own event shape. A two-phase deployment resolves request k in
        request k + 1, so its preload is whole pairs of equal width."""
        widths = [n_max] * (count // n_max) + [count % n_max] * bool(count % n_max)
        if self.two_phase and (len(widths) % 2 or count % n_max):
            raise ValueError(
                f"a two-phase deployment preloads whole pairs of {n_max}-"
                f"event requests; preloaded_count {count} is not one")
        return widths

    def _blank(self, n: int, stream: int, k: int) -> np.ndarray:
        rec = np.zeros(n, dtype=wire.TRANSFER)
        # No id repeats inside a request or across requests: the high
        # limb names seed and stream, the low limb request and event.
        rec["id_hi"] = self.id_tag | stream
        rec["id_lo"] = np.uint64(k << 16) + np.arange(1, n + 1, dtype=np.uint64)
        rec["ledger"] = self.ledger
        rec["code"] = 1
        return rec

    @staticmethod
    def _request(operation: str, rec: np.ndarray) -> Request:
        ids = np.stack([rec["id_lo"], rec["id_hi"]], axis=1)
        return Request(operation, rec.tobytes(), len(rec), ids)

    def transfer_request(self, stream: int, k: int, n: int) -> Request:
        """Request k of a stream: n events. Single-phase deployments
        send plain transfers; two-phase ones alternate a request of
        pendings (even k) with the request that posts or voids each of
        them (odd k)."""
        if self.two_phase and k % 2 == 1:
            return self._resolve(stream, k, n)
        rng = _rng(self.seed, stream, k)
        rec = self._blank(n, stream, k)
        dr = draw(self.cdf, rng, n)
        cr = draw(self.cdf, rng, n)
        cr = np.where(cr == dr, (cr + 1) % self.n, cr)  # a clash moves on
        rec["debit_lo"], rec["debit_hi"] = self.id_lo[dr], self.id_hi[dr]
        rec["credit_lo"], rec["credit_hi"] = self.id_lo[cr], self.id_hi[cr]
        rec["amount_lo"] = rng.integers(self.amount_lo, self.amount_hi, n)
        rec["ud32"] = rng.integers(0, 1 << 16, n)
        if self.two_phase:
            rec["flags"] = F_PENDING
        # Events built to fail, in fixed shares.
        roll = rng.random(n)
        same = roll < self.fail_edges[0]
        unknown = (roll >= self.fail_edges[0]) & (roll < self.fail_edges[1])
        ledger = (roll >= self.fail_edges[1]) & (roll < self.fail_edges[2])
        rec["credit_lo"][same] = rec["debit_lo"][same]
        rec["credit_hi"][same] = rec["debit_hi"][same]
        rec["debit_hi"][unknown] = 9
        rec["debit_lo"][unknown] = rng.integers(1, 1 << 40, int(unknown.sum()))
        rec["ledger"][ledger] = self.ledger + 1
        return self._request("create_transfers", rec)

    def lookup_request(self, stream: int, k: int, n: int) -> Request:
        """Request k of a stream as a read: `lookup_accounts` of n ids
        drawn by the deployment's key skew over the account index,
        repeats kept (a row is answered per id found, in request order),
        the deployment's `unknown_account` share of them ids that no
        account has."""
        rng = _rng(self.seed, stream, k)
        at = draw(self.cdf, rng, n)
        ids = np.stack([self.id_lo[at], self.id_hi[at]], axis=1)
        unknown = rng.random(n) < self.unknown_share
        ids[unknown, 1] = 9
        ids[unknown, 0] = rng.integers(1, 1 << 40, int(unknown.sum()))
        return Request("lookup_accounts", ids.astype("<u8").tobytes(), n, ids,
                       event_size=wire.ID_SIZE, result=wire.ACCOUNT)

    def session_request(self, mix: dict, stream: int, k: int, n_events: int,
                        n_ids: int) -> Request:
        """Request k of a session under a traffic mix: the read where
        the mix's `reads` block puts one (the last of every `every`
        requests), the write it would have been otherwise."""
        reads = mix.get("reads")
        if reads and k % reads["every"] == reads["every"] - 1:
            return self.lookup_request(stream, k, n_ids)
        return self.transfer_request(stream, k, n_events)

    def _resolve(self, stream: int, k: int, n: int) -> Request:
        prev = np.frombuffer(
            self.transfer_request(stream, k - 1, n).payload,
            dtype=wire.TRANSFER)
        rng = _rng(self.seed, stream, k)
        rec = self._blank(n, stream, k)
        rec["pending_lo"], rec["pending_hi"] = prev["id_lo"], prev["id_hi"]
        post = rng.random(n) < self.two_phase["post_share"]
        rec["flags"] = np.where(post, F_POST, F_VOID)
        rec["amount_lo"] = np.where(post, prev["amount_lo"], 0)
        return self._request("create_transfers", rec)
