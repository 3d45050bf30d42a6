"""The plain reference of the ChaCha20 WASI command (`build_chacha20_wasi`,
export `chacha20_write`): RFC 8439's block function and encryption in
numpy uint32, word by word, and the bytes the command puts on fd 1,
independent of every engine and of `references/chacha20.py` (another
configuration's copy; nothing is imported from it).

    2.1  QUARTERROUND(a, b, c, d):
             a += b; d ^= a; d <<<= 16;   c += d; b ^= c; b <<<= 12;
             a += b; d ^= a; d <<<= 8;    c += d; b ^= c; b <<<= 7;
    2.3  state = constants | key | counter | nonce    (16 words)
         ten times: QUARTERROUND on the columns (0, 4, 8, 12) ..
                    (3, 7, 11, 15), then on the diagonals
                    (0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13),
                    (3, 4, 9, 14)
         block = working state + state, word by word, little-endian
    2.4  ciphertext block j = message block j ^ block(key, 1 + j, nonce)

`block_words` reproduces all sixteen words of test vector 2.3.2
(tests/test_chacha20_wasi_config.py).

What the configuration set itself (`assumed`), the same as the guest:
- the seed's recurrence: w' = w * 1664525 + 1013904223 mod 2**32 from
  w = seed gives the eight key words, then the three nonce words; the
  next value, in all four lanes, times (0x9E3779B1, 0x85EBCA6B,
  0xC2B2AE35, 0x27D4EB2F) plus (1, 2, 3, 4) is the message's first four
  words, and every next four are the same step applied to each word;
- the fold that is the export's i64 result: acc = rotl(acc, 1) ^ (64
  bits of ciphertext, little-endian) over the whole ciphertext;
- the output: after every `chunk_blocks` blocks the command hands the
  64 * chunk_blocks bytes of ciphertext just produced to one
  `fd_write(1, ...)`.  The lanes of an engine share one fd 1, and the
  system documents the order in which a round of calls reaches it
  (`host/wasi/vectorized.py` `vec_fd_write`: "one write per fd,
  lane-ascending"): so a job's stream is, for each call index r in
  turn, for each lane ascending, that lane's ciphertext bytes
  [chunk r, chunk r + 1).

Vectorised over lanes and over a lane's blocks; a word's arithmetic is
the scalar loop's, operation for operation.  Lanes go through in chunks
of 64, each chunk's ciphertext folded and laid into the stream at once,
so the ciphertext is computed once for both answers.

`reference_lanes` gives every lane's raw 64-bit result cell,
`reference_stream` the bytes of a job, `reference_job` both from one
pass; `reference(func, args)` answers one lane at the listed sizes.
"""

import numpy as np

BLOCKS = 3072
CHUNK_BLOCKS = 128
SIGMA = np.frombuffer(b"expand 32-byte k", "<u4")
LCG_MUL, LCG_ADD = np.uint32(1664525), np.uint32(1013904223)
MSG_MUL = np.array([0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F],
                   np.uint32)
MSG_ADD = np.array([1, 2, 3, 4], np.uint32)
COLUMNS = ((0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15))
DIAGONALS = ((0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14))
LANE_CHUNK = 64


def rotl32(x, n):
    return (x << np.uint32(n)) | (x >> np.uint32(32 - n))


def quarter_round(x, a, b, c, d):
    """Section 2.1 on the words a, b, c, d of the list `x` (arrays of
    its own, updated in place)."""
    for p, q, r, n in ((a, b, d, 16), (c, d, b, 12),
                       (a, b, d, 8), (c, d, b, 7)):
        x[p] += x[q]
        x[r] ^= x[p]
        x[r] = rotl32(x[r], n)


def block_words(key, counter, nonce):
    """Section 2.3: the sixteen words of the key-stream block, each an
    array of the shape `key[i]`, `counter` and `nonce[i]` broadcast to
    (uint32; a block's serialisation is these words, little-endian)."""
    state = [np.uint32(w) for w in SIGMA] + list(key) + [counter] \
        + list(nonce)
    shape = np.broadcast(*state).shape
    state = [np.broadcast_to(np.asarray(w, np.uint32), shape)
             for w in state]
    x = [w.copy() for w in state]
    for _ in range(10):
        for index in COLUMNS + DIAGONALS:
            quarter_round(x, *index)
    return [xi + si for xi, si in zip(x, state)]


def derive(seeds, blocks):
    """-> (key [8], nonce [3], message uint32[lanes, blocks * 16]) from
    the lanes' seeds, by the recurrence above."""
    w = np.asarray(seeds).astype(np.uint32)
    words = []
    for _ in range(12):
        w = w * LCG_MUL + LCG_ADD
        words.append(w)
    m = words[11][:, None] * MSG_MUL + MSG_ADD          # [lanes, 4]
    message = np.empty((len(w), blocks * 4, 4), np.uint32)
    for j in range(blocks * 4):
        message[:, j] = m
        m = m * LCG_MUL + LCG_ADD
    return words[:8], words[8:11], message.reshape(len(w), -1)


def encrypt(seeds, blocks=BLOCKS):
    """Section 2.4, initial counter 1: the ciphertext of every lane's
    message as uint32[lanes, blocks * 16]."""
    key, nonce, message = derive(seeds, blocks)
    counter = np.arange(1, blocks + 1, dtype=np.uint32)[None, :]
    stream = block_words([k[:, None] for k in key], counter,
                         [n[:, None] for n in nonce])
    text = message.reshape(len(message), blocks, 16)
    for i, word in enumerate(stream):   # word i of every block
        text[:, :, i] ^= word
    return message


def fold(words):
    """acc = rotl(acc, 1) ^ doubleword over each lane's uint32 words,
    two a little-endian doubleword: uint64[lanes]."""
    acc = np.zeros(words.shape[0], np.uint64)
    one, back = np.uint64(1), np.uint64(63)
    for bits in np.ascontiguousarray(words).view("<u8").T:
        acc = ((acc << one) | (acc >> back)) ^ bits
    return acc


def _calls(blocks, chunk_blocks):
    if chunk_blocks <= 0 or blocks % chunk_blocks:
        raise ValueError(f"blocks {blocks} is no multiple of "
                         f"chunk_blocks {chunk_blocks}")
    return blocks // chunk_blocks


def reference_job(func, lane_args, blocks=BLOCKS,
                  chunk_blocks=CHUNK_BLOCKS, stream=True):
    """-> (uint64[lanes] result cells, uint8[calls * lanes * 64 *
    chunk_blocks] bytes on fd 1 or None), the ciphertext computed
    once."""
    if func != "chacha20_write":
        raise KeyError(func)
    calls = _calls(blocks, chunk_blocks)
    seeds = np.asarray(lane_args, np.int64)
    cells = np.empty(len(seeds), np.uint64)
    out = np.empty((calls, len(seeds), 16 * chunk_blocks), "<u4") \
        if stream else None
    with np.errstate(over="ignore"):
        for i in range(0, len(seeds), LANE_CHUNK):
            text = encrypt(seeds[i:i + LANE_CHUNK], blocks)
            cells[i:i + LANE_CHUNK] = fold(text)
            if stream:
                out[:, i:i + LANE_CHUNK] = text.reshape(
                    len(text), calls, -1).transpose(1, 0, 2)
    return cells, out.reshape(-1).view(np.uint8) if stream else None


def reference_lanes(func, lane_args, blocks=BLOCKS,
                    chunk_blocks=CHUNK_BLOCKS):
    """Every lane's raw result cell in one call: uint64[lanes]."""
    return reference_job(func, lane_args, blocks, chunk_blocks,
                         stream=False)[0]


def reference_stream(lane_args, blocks=BLOCKS, chunk_blocks=CHUNK_BLOCKS):
    """The bytes a job puts on fd 1: uint8, call index by call index,
    lane-ascending inside a call, 64 * chunk_blocks bytes a record."""
    return reference_job("chacha20_write", lane_args, blocks,
                         chunk_blocks)[1]


def reference(func, args):
    return [int(reference_lanes(func, [int(args[0])])[0])]
