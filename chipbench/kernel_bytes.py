"""The least bytes one create_transfers dispatch of n events has to move
through HBM, from n and the row widths alone.

It does not read the program's lowering, so it says the same whatever
implements the kernel. There is no matmul in the ledger kernels, so the
roofline that binds is HBM bandwidth. Widths are the device store's row
formats (tigerbeetle_tpu/ops/ev_layout.py and ops/ledger.py init_state
at PR 26, copied as constants; u64 columns):

    event in            128 B   the wire transfer
    result out           16 B   (timestamp, status)
    account row          8 cols  AC_NCOLS   read, debit and credit side
    balance row         16 cols             read and written, both sides
    transfer row        20 cols  XF_NCOLS   written
    history row         25 cols  EV_NCOLS   written
    hash slot            3 cols  (key_hi, key_lo, val): one probe each
                                 for the transfer id and the two account
                                 ids, one slot written for the new id
"""

EVENT_BYTES = 128
RESULT_BYTES = 16
AC_NCOLS, BAL_NCOLS, XF_NCOLS, EV_NCOLS, HT_SLOT_COLS = 8, 16, 20, 25, 3
U64 = 8


def create_transfers_bytes_per_event() -> int:
    accounts = 2 * (AC_NCOLS + 2 * BAL_NCOLS) * U64   # 2 x (64 + 256)
    rows_out = (XF_NCOLS + EV_NCOLS) * U64            # 160 + 200
    probes = (3 + 1) * HT_SLOT_COLS * U64             # 96
    return EVENT_BYTES + RESULT_BYTES + accounts + rows_out + probes


def create_transfers_least_bytes(n_events: int) -> int:
    return n_events * create_transfers_bytes_per_event()
