"""The plain reference of the CoreMark 1.0 guest (`build_coremark`, export
`coremark`): EEMBC CoreMark's `core_main.c`, `core_list_join.c`,
`core_matrix.c`, `core_state.c` and `core_util.c` followed function for
function in plain Python, over a `bytearray` that stands for the
`MEM_STATIC` block and the stack frames, independent of every engine.

"Pointers" are offsets into that memory; a list cell is two 32-bit
pointers (`next`, `info`: wasm32's `list_head`), a `list_data` two
`ee_s16` (`data16`, `idx`), the matrices `ee_s16` (MATDAT) and `ee_s32`
(MATRES), the state input bytes.  Every C integer type keeps its width:
`ee_u16` and `ee_s16` wrap at 16 bits, `ee_u8` at 8, `ee_s32` and
`ee_u32` at 32, as C's conversions say.  NULL is offset 0, where nothing
of the block lies.

    main:     seeds 0, 0, 0x66 (PERFORMANCE_RUN), TOTAL_DATA_SIZE 2000,
              all three algorithms: 666 bytes each of the block; the
              three inits; iterate; seedcrc over the seeds and the size
    iterate:  crc = crcu16(core_bench_list(res, 1), crc);
              crc = crcu16(core_bench_list(res, -1), crc)  a time,
              crclist = crc after the first

The answer packs crcfinal | crclist << 16 | crcmatrix << 32 |
crcstate << 48, the raw 64-bit cell a wasm i64 result occupies.
`reference(func, args, **guest_args)` answers one lane, `reference_lanes`
every lane (CoreMark's run is defined by its seeds, so lanes with one
iteration count share one answer), `seedcrc` the report's seed CRC.
CoreMark's own known CRCs (`core_main.c`, known_id 3 and 4) are
`KNOWN`.
"""

import struct

TOTAL_DATA_SIZE = 2000
PERFORMANCE_SEEDS = (0x0, 0x0, 0x66)
VALIDATION_SEEDS = (0x3415, 0x3415, 0x66)
# core_main.c's tables at known_id 3 ("2K performance run parameters")
# and 4 ("2K validation run parameters"): seedcrc -> (list, matrix, state)
KNOWN = {0xe9f5: (0xe714, 0x1fd7, 0x8e3a),
         0x18f2: (0xe3c1, 0x0747, 0x8d84)}

ID_LIST, ID_MATRIX, ID_STATE = 1, 2, 4
NUM_ALGORITHMS = 3
CORE_START, CORE_INVALID, CORE_S1, CORE_S2, CORE_INT, CORE_FLOAT, \
    CORE_EXPONENT, CORE_SCIENTIFIC = range(8)
NUM_CORE_STATES = 8

INTPAT = (b"5012", b"1234", b"-874", b"+122")
FLOATPAT = (b"35.54400", b".1234500", b"-110.700", b"+0.64400")
SCIPAT = (b"5.500e+3", b"-.123e-2", b"-87e+832", b"+0.6e-12")
ERRPAT = (b"T0.3e-1F", b"-T.T++Tq", b"1T3.4e4z", b"34.0e-T^")

BLOCK = 16              # static_memblk's offset; below it nothing lives
STACK = BLOCK + 4096    # the frames' offsets, growing up from here


def u8(x):
    return x & 0xFF


def u16(x):
    return x & 0xFFFF


def s16(x):
    x &= 0xFFFF
    return x - 0x10000 if x & 0x8000 else x


def u32(x):
    return x & 0xFFFFFFFF


def s32(x):
    x &= 0xFFFFFFFF
    return x - 0x100000000 if x & 0x80000000 else x


def c_div(a, b):
    """C's `/` on signed integers: truncated toward zero."""
    q = abs(a) // abs(b)
    return q if (a < 0) == (b < 0) else -q


def c_mod(a, b):
    return a - b * c_div(a, b)


class Memory:
    """The block and the frames as bytes, little-endian."""

    def __init__(self, size):
        self.b = bytearray(size)
        self.sp = STACK

    def u8(self, a):
        return self.b[a]

    def set8(self, a, v):
        self.b[a] = v & 0xFF

    def s16(self, a):
        return struct.unpack_from("<h", self.b, a)[0]

    def set16(self, a, v):
        struct.pack_into("<H", self.b, a, v & 0xFFFF)

    def s32(self, a):
        return struct.unpack_from("<i", self.b, a)[0]

    def u32(self, a):
        return struct.unpack_from("<I", self.b, a)[0]

    def set32(self, a, v):
        struct.pack_into("<I", self.b, a, v & 0xFFFFFFFF)

    def alloca(self, size):
        a = self.sp
        self.sp += (size + 15) & ~15
        return a


# -- core_util.c --------------------------------------------------------------

def crcu8(data, crc):
    data, crc = u8(data), u16(crc)
    for _ in range(8):
        x16 = u8((data & 1) ^ (u8(crc) & 1))
        data >>= 1
        if x16 == 1:
            crc ^= 0x4002
            carry = 1
        else:
            carry = 0
        crc >>= 1
        if carry:
            crc |= 0x8000
        else:
            crc &= 0x7fff
    return crc


def crcu16(newval, crc):
    crc = crcu8(u8(newval), crc)
    crc = crcu8(u8(u16(newval) >> 8), crc)
    return crc


def crcu32(newval, crc):
    crc = crc16(s16(newval), crc)
    crc = crc16(s16(u32(newval) >> 16), crc)
    return crc


def crc16(newval, crc):
    return crcu16(u16(newval), crc)


# -- core_results, as wasm32 lays it out --------------------------------------

class Results:
    """`core_results`: the fields the benchmark reads and writes."""

    def __init__(self):
        self.seed1 = self.seed2 = self.seed3 = 0
        self.memblock = [0, 0, 0, 0]
        self.size = 0
        self.iterations = 0
        self.execs = 0
        self.list = 0
        self.mat = None             # (N, A, B, C)
        self.crc = self.crclist = self.crcmatrix = self.crcstate = 0


# -- core_list_join.c ---------------------------------------------------------
# list_head: next at +0, info at +4 (8 bytes); list_data: data16 at +0,
# idx at +2 (4 bytes)

def _next(m, h):
    return m.u32(h)


def _info(m, h):
    return m.u32(h + 4)


def calc_func(m, pdata, res):
    data = m.s16(pdata)
    optype = (data >> 7) & 1
    if optype:
        return data & 0x007f
    flag = data & 0x7
    dtype = (data >> 3) & 0xf
    dtype = s16(dtype | (dtype << 4))
    if flag == 0:
        if dtype < 0x22:
            dtype = 0x22
        retval = core_bench_state(m, res.size, res.memblock[3], res.seed1,
                                  res.seed2, dtype, res.crc)
        if res.crcstate == 0:
            res.crcstate = retval
    elif flag == 1:
        retval = core_bench_matrix(m, res.mat, dtype, res.crc)
        if res.crcmatrix == 0:
            res.crcmatrix = retval
    else:
        retval = data
    res.crc = crcu16(retval, res.crc)
    retval = s16(retval & 0x007f)
    m.set16(pdata, (data & 0xff00) | 0x0080 | retval)
    return retval


def cmp_complex(m, a, b, res):
    val1 = calc_func(m, a, res)
    val2 = calc_func(m, b, res)
    return s32(val1 - val2)


def cmp_idx(m, a, b, res):
    if res is None:
        m.set16(a, (m.s16(a) & 0xff00) | (0x00ff & (m.s16(a) >> 8)))
        m.set16(b, (m.s16(b) & 0xff00) | (0x00ff & (m.s16(b) >> 8)))
    return s32(m.s16(a + 2) - m.s16(b + 2))


def copy_info(m, to, frm):
    m.set16(to, m.s16(frm))
    m.set16(to + 2, m.s16(frm + 2))


def core_bench_list(m, res, finder_idx):
    retval = 0
    found = missed = 0
    lst = res.list
    find_num = res.seed3
    info = m.alloca(4)
    m.set16(info + 2, finder_idx)
    i = 0
    while i < find_num:
        m.set16(info, i & 0xff)
        this_find = core_list_find(m, lst, info)
        lst = core_list_reverse(m, lst)
        if this_find == 0:
            missed = u16(missed + 1)
            retval = u16(retval
                         + ((m.s16(_info(m, _next(m, lst))) >> 8) & 1))
        else:
            found = u16(found + 1)
            if m.s16(_info(m, this_find)) & 0x1:
                retval = u16(retval
                             + ((m.s16(_info(m, this_find)) >> 9) & 1))
            if _next(m, this_find) != 0:
                finder = _next(m, this_find)
                m.set32(this_find, _next(m, finder))
                m.set32(finder, _next(m, lst))
                m.set32(lst, finder)
        if m.s16(info + 2) >= 0:
            m.set16(info + 2, m.s16(info + 2) + 1)
        i = s16(i + 1)
    retval = u16(retval + found * 4 - missed)
    if finder_idx > 0:
        lst = core_list_mergesort(m, lst, cmp_complex, res)
    remover = core_list_remove(m, _next(m, lst))
    finder = core_list_find(m, lst, info)
    if not finder:
        finder = _next(m, lst)
    while finder:
        retval = crc16(m.s16(_info(m, lst)), retval)
        finder = _next(m, finder)
    remover = core_list_undo_remove(m, remover, _next(m, lst))
    lst = core_list_mergesort(m, lst, cmp_idx, None)
    finder = _next(m, lst)
    while finder:
        retval = crc16(m.s16(_info(m, lst)), retval)
        finder = _next(m, finder)
    m.sp -= 16
    return retval


def core_list_init(m, blksize, memblock, seed):
    per_item = 16 + 4
    size = u32(blksize // per_item - 2)
    memblock_end = memblock + size * 8
    datablock = memblock_end
    datablock_end = datablock + size * 4
    lst = memblock
    m.set32(lst, 0)
    m.set32(lst + 4, datablock)
    m.set16(datablock + 2, 0x0000)
    m.set16(datablock, 0x8080)
    memblock += 8
    datablock += 4
    info = m.alloca(4)
    m.set16(info + 2, 0x7fff)
    m.set16(info, 0xffff)
    blocks = [memblock, datablock]
    core_list_insert_new(m, lst, info, blocks, memblock_end, datablock_end)
    for i in range(size):
        datpat = u16(seed ^ i) & 0xf
        dat = (datpat << 3) | (i & 0x7)
        m.set16(info, (dat << 8) | dat)
        core_list_insert_new(m, lst, info, blocks, memblock_end,
                             datablock_end)
    finder = _next(m, lst)
    i = 1
    while _next(m, finder) != 0:
        if i < size // 5:
            m.set16(_info(m, finder) + 2, i)
            i += 1
        else:
            pat = u16(i ^ seed)
            i += 1
            m.set16(_info(m, finder) + 2,
                    0x3fff & (((i & 0x07) << 8) | pat))
        finder = _next(m, finder)
    lst = core_list_mergesort(m, lst, cmp_idx, None)
    m.sp -= 16
    return lst


def core_list_insert_new(m, insert_point, info, blocks, memblock_end,
                         datablock_end):
    """`blocks` is [*memblock, *datablock], the two pointers the C
    passes by address."""
    if blocks[0] + 8 >= memblock_end:
        return 0
    if blocks[1] + 4 >= datablock_end:
        return 0
    newitem = blocks[0]
    blocks[0] += 8
    m.set32(newitem, _next(m, insert_point))
    m.set32(insert_point, newitem)
    m.set32(newitem + 4, blocks[1])
    blocks[1] += 4
    copy_info(m, _info(m, newitem), info)
    return newitem


def core_list_remove(m, item):
    ret = _next(m, item)
    tmp = _info(m, item)
    m.set32(item + 4, _info(m, ret))
    m.set32(ret + 4, tmp)
    m.set32(item, _next(m, _next(m, item)))
    m.set32(ret, 0)
    return ret


def core_list_undo_remove(m, item_removed, item_modified):
    tmp = _info(m, item_removed)
    m.set32(item_removed + 4, _info(m, item_modified))
    m.set32(item_modified + 4, tmp)
    m.set32(item_removed, _next(m, item_modified))
    m.set32(item_modified, item_removed)
    return item_removed


def core_list_find(m, lst, info):
    if m.s16(info + 2) >= 0:
        while lst and m.s16(_info(m, lst) + 2) != m.s16(info + 2):
            lst = _next(m, lst)
        return lst
    while lst and (m.s16(_info(m, lst)) & 0xff) != m.s16(info):
        lst = _next(m, lst)
    return lst


def core_list_reverse(m, lst):
    nxt = 0
    while lst:
        tmp = _next(m, lst)
        m.set32(lst, nxt)
        nxt = lst
        lst = tmp
    return nxt


def core_list_mergesort(m, lst, cmp, res):
    insize = 1
    while True:
        p = lst
        lst = 0
        tail = 0
        nmerges = 0
        while p:
            nmerges += 1
            q = p
            psize = 0
            for _ in range(insize):
                psize += 1
                q = _next(m, q)
                if not q:
                    break
            qsize = insize
            while psize > 0 or (qsize > 0 and q):
                if psize == 0:
                    e = q
                    q = _next(m, q)
                    qsize -= 1
                elif qsize == 0 or not q:
                    e = p
                    p = _next(m, p)
                    psize -= 1
                elif cmp(m, _info(m, p), _info(m, q), res) <= 0:
                    e = p
                    p = _next(m, p)
                    psize -= 1
                else:
                    e = q
                    q = _next(m, q)
                    qsize -= 1
                if tail:
                    m.set32(tail, e)
                else:
                    lst = e
                tail = e
            p = q
        m.set32(tail, 0)
        if nmerges <= 1:
            return lst
        insize *= 2


# -- core_matrix.c ------------------------------------------------------------

def align_mem(x):
    return 4 + ((x - 1) & ~3)


def core_bench_matrix(m, p, seed, crc):
    n, a, b, c = p
    val = s16(seed)
    crc = crc16(matrix_test(m, n, c, a, b, val), crc)
    return crc


def matrix_test(m, n, c, a, b, val):
    crc = 0
    clipval = s16(0xf000 | val)
    matrix_add_const(m, n, a, val)
    matrix_mul_const(m, n, c, a, val)
    crc = crc16(matrix_sum(m, n, c, clipval), crc)
    matrix_mul_vect(m, n, c, a, b)
    crc = crc16(matrix_sum(m, n, c, clipval), crc)
    matrix_mul_matrix(m, n, c, a, b)
    crc = crc16(matrix_sum(m, n, c, clipval), crc)
    matrix_mul_matrix_bitextract(m, n, c, a, b)
    crc = crc16(matrix_sum(m, n, c, clipval), crc)
    matrix_add_const(m, n, a, s16(-val))
    return s16(crc)


def core_init_matrix(m, blksize, memblk, seed, res):
    order = 1
    i = j = 0
    if seed == 0:
        seed = 1
    while j < blksize:
        i += 1
        j = i * i * 2 * 4
    n = i - 1
    a = align_mem(memblk)
    b = a + n * n * 2
    for i in range(n):
        for j in range(n):
            seed = s32(c_mod(s32(order * seed), 65536))
            val = s16(seed + order)
            val = s16(val & 0x0ffff)
            m.set16(b + (i * n + j) * 2, val)
            val = s16(val + order)
            val = s16(val & 0x0ff)
            m.set16(a + (i * n + j) * 2, val)
            order += 1
    c = align_mem(b + n * n * 2)
    res.mat = (n, a, b, c)
    return n


def matrix_sum(m, n, c, clipval):
    tmp = prev = 0
    ret = 0
    for i in range(n):
        for j in range(n):
            cur = m.s32(c + (i * n + j) * 4)
            tmp = s32(tmp + cur)
            if tmp > clipval:
                ret = s16(ret + 10)
                tmp = 0
            else:
                ret = s16(ret + (1 if cur > prev else 0))
            prev = cur
    return ret


def matrix_mul_const(m, n, c, a, val):
    for i in range(n):
        for j in range(n):
            m.set32(c + (i * n + j) * 4, m.s16(a + (i * n + j) * 2) * val)


def matrix_add_const(m, n, a, val):
    for i in range(n):
        for j in range(n):
            at = a + (i * n + j) * 2
            m.set16(at, m.s16(at) + val)


def matrix_mul_vect(m, n, c, a, b):
    for i in range(n):
        acc = 0
        for j in range(n):
            acc = s32(acc + m.s16(a + (i * n + j) * 2) * m.s16(b + j * 2))
        m.set32(c + i * 4, acc)


def matrix_mul_matrix(m, n, c, a, b):
    for i in range(n):
        for j in range(n):
            acc = 0
            for k in range(n):
                acc = s32(acc + m.s16(a + (i * n + k) * 2)
                          * m.s16(b + (k * n + j) * 2))
            m.set32(c + (i * n + j) * 4, acc)


def bit_extract(x, frm, to):
    return (s32(x) >> frm) & u32(~u32(0xffffffff << to))


def matrix_mul_matrix_bitextract(m, n, c, a, b):
    for i in range(n):
        for j in range(n):
            acc = 0
            for k in range(n):
                tmp = s32(m.s16(a + (i * n + k) * 2)
                          * m.s16(b + (k * n + j) * 2))
                acc = s32(acc + bit_extract(tmp, 2, 4)
                          * bit_extract(tmp, 5, 7))
            m.set32(c + (i * n + j) * 4, acc)


# -- core_state.c -------------------------------------------------------------

def core_bench_state(m, blksize, memblock, seed1, seed2, step, crc):
    final_counts = [0] * NUM_CORE_STATES
    track_counts = [0] * NUM_CORE_STATES
    p = memblock
    while m.u8(p) != 0:
        fstate, p = core_state_transition(m, p, track_counts)
        final_counts[fstate] += 1
    p = memblock
    while p < memblock + blksize:
        if m.u8(p) != ord(","):
            m.set8(p, m.u8(p) ^ u8(seed1))
        p += step
    p = memblock
    while m.u8(p) != 0:
        fstate, p = core_state_transition(m, p, track_counts)
        final_counts[fstate] += 1
    p = memblock
    while p < memblock + blksize:
        if m.u8(p) != ord(","):
            m.set8(p, m.u8(p) ^ u8(seed2))
        p += step
    for i in range(NUM_CORE_STATES):
        crc = crcu32(final_counts[i], crc)
        crc = crcu32(track_counts[i], crc)
    return crc


def core_init_state(m, size, seed, p):
    total = nxt = 0
    buf = b""
    size -= 1
    while total + nxt + 1 < size:
        if nxt > 0:
            for i in range(nxt):
                m.set8(p + total + i, buf[i])
            m.set8(p + total + nxt, ord(","))
            total += nxt + 1
        seed = s16(seed + 1)
        kind = seed & 0x7
        if kind in (0, 1, 2):
            buf, nxt = INTPAT[(seed >> 3) & 0x3], 4
        elif kind in (3, 4):
            buf, nxt = FLOATPAT[(seed >> 3) & 0x3], 8
        elif kind in (5, 6):
            buf, nxt = SCIPAT[(seed >> 3) & 0x3], 8
        else:
            buf, nxt = ERRPAT[(seed >> 3) & 0x3], 8
    size += 1
    while total < size:
        m.set8(p + total, 0)
        total += 1


def ee_isdigit(c):
    return 1 if ord("0") <= c <= ord("9") else 0


def core_state_transition(m, s, transition_count):
    """-> (the state it ended in, the new `*instr`)."""
    state = CORE_START
    while m.u8(s) and state != CORE_INVALID:
        c = m.u8(s)
        if c == ord(","):
            s += 1
            break
        if state == CORE_START:
            if ee_isdigit(c):
                state = CORE_INT
            elif c in (ord("+"), ord("-")):
                state = CORE_S1
            elif c == ord("."):
                state = CORE_FLOAT
            else:
                state = CORE_INVALID
                transition_count[CORE_INVALID] += 1
            transition_count[CORE_START] += 1
        elif state == CORE_S1:
            if ee_isdigit(c):
                state = CORE_INT
            elif c == ord("."):
                state = CORE_FLOAT
            else:
                state = CORE_INVALID
            transition_count[CORE_S1] += 1
        elif state == CORE_INT:
            if c == ord("."):
                state = CORE_FLOAT
                transition_count[CORE_INT] += 1
            elif not ee_isdigit(c):
                state = CORE_INVALID
                transition_count[CORE_INT] += 1
        elif state == CORE_FLOAT:
            if c in (ord("E"), ord("e")):
                state = CORE_S2
                transition_count[CORE_FLOAT] += 1
            elif not ee_isdigit(c):
                state = CORE_INVALID
                transition_count[CORE_FLOAT] += 1
        elif state == CORE_S2:
            if c in (ord("+"), ord("-")):
                state = CORE_EXPONENT
            else:
                state = CORE_INVALID
            transition_count[CORE_S2] += 1
        elif state == CORE_EXPONENT:
            if ee_isdigit(c):
                state = CORE_SCIENTIFIC
            else:
                state = CORE_INVALID
            transition_count[CORE_EXPONENT] += 1
        elif state == CORE_SCIENTIFIC:
            if not ee_isdigit(c):
                state = CORE_INVALID
                transition_count[CORE_INVALID] += 1
        s += 1
    return state, s


# -- core_main.c --------------------------------------------------------------

def iterate(m, res):
    res.crc = res.crclist = res.crcmatrix = res.crcstate = 0
    for i in range(res.iterations):
        crc = core_bench_list(m, res, 1)
        res.crc = crcu16(crc, res.crc)
        crc = core_bench_list(m, res, -1)
        res.crc = crcu16(crc, res.crc)
        if i == 0:
            res.crclist = res.crc


def run(iterations, total_data_size=TOTAL_DATA_SIZE, seed1=0, seed2=0,
        seed3=0x66):
    """main between portable_init and the report -> (crcfinal, crclist,
    crcmatrix, crcstate, seedcrc)."""
    m = Memory(STACK + 4096)
    res = Results()
    res.seed1, res.seed2, res.seed3 = s16(seed1), s16(seed2), s16(seed3)
    res.iterations = u32(iterations)
    res.execs = 0
    if res.execs == 0:
        res.execs = ID_LIST | ID_MATRIX | ID_STATE
    if res.seed1 == 0 and res.seed2 == 0 and res.seed3 == 0:
        res.seed1, res.seed2, res.seed3 = 0, 0, 0x66
    if res.seed1 == 1 and res.seed2 == 0 and res.seed3 == 0:
        res.seed1, res.seed2, res.seed3 = 0x3415, 0x3415, 0x66
    res.memblock[0] = BLOCK
    res.size = total_data_size
    num_algorithms = sum(1 for i in range(NUM_ALGORITHMS)
                         if (1 << i) & res.execs)
    res.size = res.size // num_algorithms
    j = 0
    for i in range(NUM_ALGORITHMS):
        if (1 << i) & res.execs:
            res.memblock[i + 1] = res.memblock[0] + res.size * j
            j += 1
    if res.execs & ID_LIST:
        res.list = core_list_init(m, res.size, res.memblock[1], res.seed1)
    if res.execs & ID_MATRIX:
        core_init_matrix(m, res.size, res.memblock[2],
                         s32(res.seed1 | (res.seed2 << 16)), res)
    if res.execs & ID_STATE:
        core_init_state(m, res.size, res.seed1, res.memblock[3])
    iterate(m, res)
    seedcrc = 0
    seedcrc = crc16(res.seed1, seedcrc)
    seedcrc = crc16(res.seed2, seedcrc)
    seedcrc = crc16(res.seed3, seedcrc)
    seedcrc = crc16(res.size, seedcrc)
    return res.crc, res.crclist, res.crcmatrix, res.crcstate, seedcrc


def pack(crcfinal, crclist, crcmatrix, crcstate):
    return crcfinal | crclist << 16 | crcmatrix << 32 | crcstate << 48


def reference(func, args, **guest_args):
    assert func == "coremark", func
    return [pack(*run(int(args[0]), **guest_args)[:4])]


def reference_lanes(func, lane_args, **guest_args):
    """Every lane's raw 64-bit cell; lanes with one iteration count share
    one run."""
    answers = {int(a): reference(func, [a], **guest_args)[0]
               for a in set(int(a) for a in lane_args)}
    return [answers[int(a)] for a in lane_args]
