// Native scalar engine: the C++ dispatch loop over the lowered SoA image.
//
// Structural mirror of the reference interpreter's hot loop
// (/root/reference/lib/executor/engine/engine.cpp:68-1641): `while (true)`
// over a flat pre-lowered instruction array with a single switch dispatch,
// branch = stack-erase + pc assignment (helper.cpp:179-193), call = frame
// push with zero-filled locals (helper.cpp:153-176).  Executes the same
// LoweredModule image as the Python oracle and the TPU engines; semantics
// are bit-exact with executor/numeric.py (NaN canonicalization on float
// arithmetic, trapping truncation bounds, masked shifts, trunc division).
//
// Scope: the full scalar ISA (i32/i64/f32/f64 numerics + control +
// memory), the table/segment families (get/set/size/grow/fill/copy/init,
// elem.drop, memory.init/data.drop — reference tableInstr.cpp) and tail
// calls (frame replacement, stackmgr.h:80-98), for single-module,
// single-table, no-host-import execution.  SIMD, cross-module calls and
// host functions stay on the Python engine — the ctypes wrapper
// (native/__init__.py) gates eligibility from this file's own `case`
// labels and falls back, the same graceful degradation the reference
// applies to mismatched AOT sections (lib/loader/ast/module.cpp:279-326).
// Table/segment mutations write back to the instance, so invokes
// interleave with the other engines without state divergence.
//
// Opcode ids come from gen_opcodes.h, generated from the Python opcode
// table at build time so the two sides can never drift.

#include <cmath>
#include <cstdint>
#include <cstring>

#include "gen_opcodes.h"

typedef uint64_t cell;

static inline int32_t s32(cell v) { return (int32_t)(uint32_t)v; }
static inline int64_t s64(cell v) { return (int64_t)v; }
static inline cell u32c(uint32_t v) { return (cell)v; }

static inline float f32_of(cell v) {
  float f;
  uint32_t b = (uint32_t)v;
  std::memcpy(&f, &b, 4);
  return f;
}
static inline cell bits_f32(float f) {
  uint32_t b;
  std::memcpy(&b, &f, 4);
  return (cell)b;
}
static inline double f64_of(cell v) {
  double d;
  std::memcpy(&d, &v, 8);
  return d;
}
static inline cell bits_f64(double d) {
  cell b;
  std::memcpy(&b, &d, 8);
  return b;
}
static inline cell canon32(cell bits) {
  if ((bits & 0x7F800000u) == 0x7F800000u && (bits & 0x007FFFFFu))
    return 0x7FC00000u;
  return bits;
}
static inline cell canon64(cell bits) {
  if ((bits & 0x7FF0000000000000ull) == 0x7FF0000000000000ull &&
      (bits & 0x000FFFFFFFFFFFFFull))
    return 0x7FF8000000000000ull;
  return bits;
}

struct Frame {
  int32_t ret_pc;
  int64_t fp;
  int64_t opbase;
};

extern "C" int32_t we_native_invoke(
    // image (all read-only)
    const int32_t* ops, const int32_t* aa, const int32_t* bb,
    const int32_t* cc, const int64_t* imm, int32_t code_len,
    const int32_t* brt, const int32_t* f_entry, const int32_t* f_nparams,
    const int32_t* f_nlocals, const int32_t* f_nresults,
    const int32_t* f_ftop, const int32_t* f_typeid, int32_t nf,
    const int32_t* typeid_of_type,
    // table 0: mutable entries + size (funcidx+1 handles, 0 = null);
    // tcap bounds table.grow (declared max clamped by the wrapper)
    int32_t* table, int32_t* tsize_io, int32_t tcap,
    // passive segments for table.init / memory.init; drop flags are
    // written back so segment drops persist on the instance
    const int32_t* elem_flat, const int32_t* elem_off,
    const int32_t* elem_len, int32_t n_eseg, uint8_t* edrop,
    const uint8_t* data_flat, const int32_t* data_off,
    const int32_t* data_len, int32_t n_dseg, uint8_t* ddrop,
    // mutable instance state
    cell* globals, uint8_t* mem, int32_t cur_pages, int32_t max_pages,
    // invocation
    int32_t func_idx, const cell* args, int32_t nargs, cell* results,
    int32_t max_call_depth, int64_t max_value_stack,
    const volatile int32_t* stop_flag,
    // outputs
    int64_t* retired_out, int32_t* out_pages) {
  int32_t trapcode = 0;
  int64_t retired = 0;
  int32_t tsize = tsize_io ? *tsize_io : 0;
  cell* st = new cell[max_value_stack];
  Frame* frames = new Frame[max_call_depth + 2];
  int64_t sp = 0;  // next free slot
  int32_t depth = 0;

  const int32_t entry_nlocals = f_nlocals[func_idx];
  const int32_t entry_nres = f_nresults[func_idx];
  (void)entry_nres;
  if ((int64_t)entry_nlocals + f_ftop[func_idx] > max_value_stack) {
    delete[] st;
    delete[] frames;
    *retired_out = 0;
    *out_pages = cur_pages;
    return E_StackOverflow;
  }
  for (int32_t i = 0; i < nargs; i++) st[sp++] = args[i];
  for (int32_t i = nargs; i < entry_nlocals; i++) st[sp++] = 0;
  int64_t fp = 0;
  int64_t opbase = entry_nlocals;
  int32_t pc = f_entry[func_idx];

#define TRAP(code)     \
  do {                 \
    trapcode = (code); \
    goto done;         \
  } while (0)
#define CHECK_STOP() \
  if (stop_flag && *stop_flag) TRAP(E_Terminated)
#define PUSH(v) st[sp++] = (v)
#define POP() st[--sp]
#define TOP() st[sp - 1]
#define MEM_BYTES ((int64_t)cur_pages << 16)

  // typed memory access with bounds checks (software guard: SURVEY §5.2)
#define LOADN(n, dst)                                             \
  do {                                                            \
    uint64_t _ea = (uint64_t)(uint32_t)TOP() + (uint64_t)imm[pc]; \
    if (_ea + (n) > (uint64_t)MEM_BYTES) TRAP(E_MemoryOOB);       \
    uint64_t _lv = 0;                                             \
    std::memcpy(&_lv, mem + _ea, (n));                            \
    dst = _lv;                                                    \
  } while (0)
#define STOREN(n)                                                 \
  do {                                                            \
    cell _sv = POP();                                             \
    uint64_t _ea = (uint64_t)(uint32_t)POP() + (uint64_t)imm[pc]; \
    if (_ea + (n) > (uint64_t)MEM_BYTES) TRAP(E_MemoryOOB);       \
    std::memcpy(mem + _ea, &_sv, (n));                            \
  } while (0)

  // binary-op plumbing
#define BIN32(expr)                                    \
  do {                                                 \
    uint32_t b = (uint32_t)POP(), a = (uint32_t)TOP(); \
    (void)a;                                           \
    (void)b;                                           \
    TOP() = u32c((uint32_t)(expr));                    \
  } while (0)
#define BIN64(expr)                        \
  do {                                     \
    cell b = POP(), a = TOP();             \
    (void)a;                               \
    (void)b;                               \
    TOP() = (cell)((uint64_t)(expr));      \
  } while (0)
#define FBIN32(expr)                          \
  do {                                        \
    float b = f32_of(POP()), a = f32_of(TOP()); \
    TOP() = canon32(bits_f32((expr)));        \
  } while (0)
#define FBIN64(expr)                            \
  do {                                          \
    double b = f64_of(POP()), a = f64_of(TOP()); \
    TOP() = canon64(bits_f64((expr)));          \
  } while (0)
#define FCMP32(expr)                            \
  do {                                          \
    float b = f32_of(POP()), a = f32_of(TOP()); \
    TOP() = (expr) ? 1 : 0;                     \
  } while (0)
#define FCMP64(expr)                              \
  do {                                            \
    double b = f64_of(POP()), a = f64_of(TOP()); \
    TOP() = (expr) ? 1 : 0;                      \
  } while (0)
#define FUN32(expr)            \
  do {                         \
    float a = f32_of(TOP());   \
    TOP() = canon32(bits_f32((expr))); \
  } while (0)
#define FUN64(expr)            \
  do {                         \
    double a = f64_of(TOP());  \
    TOP() = canon64(bits_f64((expr))); \
  } while (0)

  while (true) {
    const int32_t op = ops[pc];
    retired++;
    switch (op) {
      // ---- locals / consts / parametric -----------------------------
      case OP_local_get:
        PUSH(st[fp + aa[pc]]);
        pc++;
        break;
      case OP_local_set:
        st[fp + aa[pc]] = POP();
        pc++;
        break;
      case OP_local_tee:
        st[fp + aa[pc]] = TOP();
        pc++;
        break;
      case OP_i32_const:
      case OP_i64_const:
      case OP_f32_const:
      case OP_f64_const:
        PUSH((cell)imm[pc]);
        pc++;
        break;
      case OP_drop:
        sp--;
        pc++;
        break;
      case OP_select: {
        cell c = POP();
        cell v2 = POP();
        if (c == 0) TOP() = v2;
        pc++;
        break;
      }
      case OP_global_get:
        PUSH(globals[aa[pc]]);
        pc++;
        break;
      case OP_global_set:
        globals[aa[pc]] = POP();
        pc++;
        break;
      case OP_nop:
        pc++;
        break;
      case OP_unreachable:
        TRAP(E_Unreachable);
      case OP_ref_null:
        PUSH(0);
        pc++;
        break;
      case OP_ref_is_null:
        TOP() = TOP() == 0 ? 1 : 0;
        pc++;
        break;

      // ---- control --------------------------------------------------
      case LOP_BR_ID: {
        CHECK_STOP();
        int32_t keep = bb[pc];
        cell kept[16];
        for (int32_t k = 0; k < keep; k++) kept[k] = st[sp - keep + k];
        sp = opbase + cc[pc];
        for (int32_t k = 0; k < keep; k++) st[sp++] = kept[k];
        pc = aa[pc];
        break;
      }
      case LOP_BRZ_ID:
        if (POP() == 0)
          pc = aa[pc];
        else
          pc++;
        break;
      case LOP_BRNZ_ID:
        if (POP() != 0) {
          CHECK_STOP();
          int32_t keep = bb[pc];
          cell kept[16];
          for (int32_t k = 0; k < keep; k++) kept[k] = st[sp - keep + k];
          sp = opbase + cc[pc];
          for (int32_t k = 0; k < keep; k++) st[sp++] = kept[k];
          pc = aa[pc];
        } else {
          pc++;
        }
        break;
      case OP_br_table: {
        CHECK_STOP();
        uint32_t i = (uint32_t)POP();
        uint32_t n = (uint32_t)bb[pc];
        int64_t entry = ((int64_t)aa[pc] + (i < n ? i : n)) * 3;
        int32_t keep = brt[entry + 1];
        cell kept[16];
        for (int32_t k = 0; k < keep; k++) kept[k] = st[sp - keep + k];
        sp = opbase + brt[entry + 2];
        for (int32_t k = 0; k < keep; k++) st[sp++] = kept[k];
        pc = brt[entry];
        break;
      }
      case OP_return: {
        int32_t n = bb[pc];
        cell kept[16];
        for (int32_t k = 0; k < n; k++) kept[k] = st[sp - n + k];
        sp = fp;
        for (int32_t k = 0; k < n; k++) st[sp++] = kept[k];
        if (depth == 0) {
          for (int32_t k = 0; k < n; k++) results[k] = st[sp - n + k];
          goto done;
        }
        depth--;
        pc = frames[depth].ret_pc;
        fp = frames[depth].fp;
        opbase = frames[depth].opbase;
        break;
      }
      case OP_call:
      case OP_call_indirect:
      case OP_return_call:
      case OP_return_call_indirect: {
        CHECK_STOP();
        bool tail = (op == OP_return_call || op == OP_return_call_indirect);
        int32_t callee;
        if (op == OP_call || op == OP_return_call) {
          callee = aa[pc];
        } else {
          uint32_t i = (uint32_t)POP();
          if (i >= (uint32_t)tsize) TRAP(E_UndefinedElement);
          int32_t h = table[i];
          if (h == 0) TRAP(E_UninitializedElement);
          callee = h - 1;
          if (f_typeid[callee] != typeid_of_type[aa[pc]])
            TRAP(E_IndirectCallTypeMismatch);
        }
        int32_t cn = f_nparams[callee];
        int32_t cl = f_nlocals[callee];
        if (tail) {
          // frame REPLACEMENT (reference StackManager tail-call path,
          // include/runtime/stackmgr.h:80-98): args slide onto the
          // caller's frame base, depth unchanged — O(1) frames for
          // arbitrarily deep tail recursion.  Ascending copy is
          // overlap-safe: src base sp-cn >= opbase >= fp.
          if (fp + cl + (int64_t)f_ftop[callee] > max_value_stack)
            TRAP(E_StackOverflow);
          for (int32_t k = 0; k < cn; k++) st[fp + k] = st[sp - cn + k];
          sp = fp + cn;
          for (int32_t k = cn; k < cl; k++) st[sp++] = 0;
          opbase = fp + cl;
          pc = f_entry[callee];
          break;
        }
        if (depth >= max_call_depth) TRAP(E_CallStackExhausted);
        frames[depth].ret_pc = pc + 1;
        frames[depth].fp = fp;
        frames[depth].opbase = opbase;
        depth++;
        fp = sp - cn;
        // per-function operand ceiling from the validator (f_frame_top),
        // the same bound the device engines check at call entry
        if (fp + cl + (int64_t)f_ftop[callee] > max_value_stack)
          TRAP(E_StackOverflow);
        for (int32_t k = cn; k < cl; k++) st[sp++] = 0;
        opbase = fp + cl;
        pc = f_entry[callee];
        break;
      }

      // ---- tables / segments (r05; reference tableInstr.cpp) --------
      case OP_ref_func:
        PUSH((cell)(uint32_t)(aa[pc] + 1));
        pc++;
        break;
      case OP_table_get: {
        uint32_t i = (uint32_t)POP();
        if (i >= (uint32_t)tsize) TRAP(E_TableOOB);
        PUSH((cell)(uint32_t)table[i]);
        pc++;
        break;
      }
      case OP_table_set: {
        cell v = POP();
        uint32_t i = (uint32_t)POP();
        if (i >= (uint32_t)tsize) TRAP(E_TableOOB);
        table[i] = (int32_t)(uint32_t)v;
        pc++;
        break;
      }
      case OP_table_size:
        PUSH((cell)(uint32_t)tsize);
        pc++;
        break;
      case OP_table_grow: {
        uint32_t delta = (uint32_t)POP();
        cell init = POP();
        uint64_t ns = (uint64_t)(uint32_t)tsize + delta;
        if (ns > (uint64_t)(uint32_t)tcap) {
          PUSH((cell)(uint32_t)(int32_t)-1);
        } else {
          for (uint32_t k = 0; k < delta; k++)
            table[tsize + (int32_t)k] = (int32_t)(uint32_t)init;
          PUSH((cell)(uint32_t)tsize);
          tsize = (int32_t)ns;
        }
        pc++;
        break;
      }
      case OP_table_fill: {
        uint32_t n = (uint32_t)POP();
        cell v = POP();
        uint32_t i = (uint32_t)POP();
        if ((uint64_t)i + n > (uint64_t)(uint32_t)tsize) TRAP(E_TableOOB);
        for (uint32_t k = 0; k < n; k++)
          table[i + k] = (int32_t)(uint32_t)v;
        pc++;
        break;
      }
      case OP_table_copy: {
        uint32_t n = (uint32_t)POP();
        uint32_t src = (uint32_t)POP();
        uint32_t dst = (uint32_t)POP();
        if ((uint64_t)src + n > (uint64_t)(uint32_t)tsize ||
            (uint64_t)dst + n > (uint64_t)(uint32_t)tsize)
          TRAP(E_TableOOB);
        std::memmove(table + dst, table + src, (size_t)n * 4);
        pc++;
        break;
      }
      case OP_table_init: {
        uint32_t n = (uint32_t)POP();
        uint32_t src = (uint32_t)POP();
        uint32_t dst = (uint32_t)POP();
        int32_t seg = aa[pc];
        uint32_t slen =
            (seg < n_eseg && !edrop[seg]) ? (uint32_t)elem_len[seg] : 0u;
        if ((uint64_t)src + n > slen ||
            (uint64_t)dst + n > (uint64_t)(uint32_t)tsize)
          TRAP(E_TableOOB);
        std::memcpy(table + dst, elem_flat + elem_off[seg] + src,
                    (size_t)n * 4);
        pc++;
        break;
      }
      case OP_elem_drop:
        if (aa[pc] < n_eseg) edrop[aa[pc]] = 1;
        pc++;
        break;
      case OP_memory_init: {
        uint32_t n = (uint32_t)POP();
        uint32_t src = (uint32_t)POP();
        uint32_t dst = (uint32_t)POP();
        int32_t seg = aa[pc];
        uint32_t slen =
            (seg < n_dseg && !ddrop[seg]) ? (uint32_t)data_len[seg] : 0u;
        if ((uint64_t)src + n > slen ||
            (uint64_t)dst + n > (uint64_t)MEM_BYTES)
          TRAP(E_MemoryOOB);
        std::memcpy(mem + dst, data_flat + data_off[seg] + src, n);
        pc++;
        break;
      }
      case OP_data_drop:
        if (aa[pc] < n_dseg) ddrop[aa[pc]] = 1;
        pc++;
        break;

      // ---- memory ---------------------------------------------------
      case OP_i32_load: {
        cell v;
        LOADN(4, v);
        TOP() = v;
        pc++;
        break;
      }
      case OP_f32_load: {
        cell v;
        LOADN(4, v);
        TOP() = v;
        pc++;
        break;
      }
      case OP_i64_load:
      case OP_f64_load: {
        cell v;
        LOADN(8, v);
        TOP() = v;
        pc++;
        break;
      }
      case OP_i32_load8_u: {
        cell v;
        LOADN(1, v);
        TOP() = v;
        pc++;
        break;
      }
      case OP_i32_load8_s: {
        cell v;
        LOADN(1, v);
        TOP() = u32c((uint32_t)(int32_t)(int8_t)v);
        pc++;
        break;
      }
      case OP_i32_load16_u: {
        cell v;
        LOADN(2, v);
        TOP() = v;
        pc++;
        break;
      }
      case OP_i32_load16_s: {
        cell v;
        LOADN(2, v);
        TOP() = u32c((uint32_t)(int32_t)(int16_t)v);
        pc++;
        break;
      }
      case OP_i64_load8_u: {
        cell v;
        LOADN(1, v);
        TOP() = v;
        pc++;
        break;
      }
      case OP_i64_load8_s: {
        cell v;
        LOADN(1, v);
        TOP() = (cell)(int64_t)(int8_t)v;
        pc++;
        break;
      }
      case OP_i64_load16_u: {
        cell v;
        LOADN(2, v);
        TOP() = v;
        pc++;
        break;
      }
      case OP_i64_load16_s: {
        cell v;
        LOADN(2, v);
        TOP() = (cell)(int64_t)(int16_t)v;
        pc++;
        break;
      }
      case OP_i64_load32_u: {
        cell v;
        LOADN(4, v);
        TOP() = v;
        pc++;
        break;
      }
      case OP_i64_load32_s: {
        cell v;
        LOADN(4, v);
        TOP() = (cell)(int64_t)(int32_t)v;
        pc++;
        break;
      }
      case OP_i32_store:
      case OP_f32_store:
        STOREN(4);
        pc++;
        break;
      case OP_i64_store:
      case OP_f64_store:
        STOREN(8);
        pc++;
        break;
      case OP_i32_store8:
      case OP_i64_store8:
        STOREN(1);
        pc++;
        break;
      case OP_i32_store16:
      case OP_i64_store16:
        STOREN(2);
        pc++;
        break;
      case OP_i64_store32:
        STOREN(4);
        pc++;
        break;
      case OP_memory_size:
        PUSH((cell)(uint32_t)cur_pages);
        pc++;
        break;
      case OP_memory_grow: {
        uint64_t delta = (uint32_t)POP();
        uint64_t nw = (uint64_t)(uint32_t)cur_pages + delta;  // no u32 wrap
        if (nw > (uint64_t)(uint32_t)max_pages || nw > 65536u) {
          PUSH(u32c((uint32_t)-1));
        } else {
          PUSH((cell)(uint32_t)cur_pages);
          std::memset(mem + ((int64_t)cur_pages << 16), 0,
                      (int64_t)delta << 16);
          cur_pages = (int32_t)nw;
        }
        pc++;
        break;
      }
      case OP_memory_copy: {
        uint64_t n = (uint32_t)POP();
        uint64_t src = (uint32_t)POP();
        uint64_t dst = (uint32_t)POP();
        if (src + n > (uint64_t)MEM_BYTES || dst + n > (uint64_t)MEM_BYTES)
          TRAP(E_MemoryOOB);
        std::memmove(mem + dst, mem + src, n);
        pc++;
        break;
      }
      case OP_memory_fill: {
        uint64_t n = (uint32_t)POP();
        uint8_t val = (uint8_t)POP();
        uint64_t dst = (uint32_t)POP();
        if (dst + n > (uint64_t)MEM_BYTES) TRAP(E_MemoryOOB);
        std::memset(mem + dst, val, n);
        pc++;
        break;
      }

      // ---- i32 numerics --------------------------------------------
      case OP_i32_add: BIN32(a + b); pc++; break;
      case OP_i32_sub: BIN32(a - b); pc++; break;
      case OP_i32_mul: BIN32(a * b); pc++; break;
      case OP_i32_and: BIN32(a & b); pc++; break;
      case OP_i32_or: BIN32(a | b); pc++; break;
      case OP_i32_xor: BIN32(a ^ b); pc++; break;
      case OP_i32_shl: BIN32(a << (b & 31)); pc++; break;
      case OP_i32_shr_u: BIN32(a >> (b & 31)); pc++; break;
      case OP_i32_shr_s: BIN32((uint32_t)((int32_t)a >> (b & 31))); pc++; break;
      case OP_i32_rotl: BIN32((b & 31) ? ((a << (b & 31)) | (a >> (32 - (b & 31)))) : a); pc++; break;
      case OP_i32_rotr: BIN32((b & 31) ? ((a >> (b & 31)) | (a << (32 - (b & 31)))) : a); pc++; break;
      case OP_i32_div_s: {
        uint32_t b = (uint32_t)POP(), a = (uint32_t)TOP();
        if (b == 0) TRAP(E_DivideByZero);
        if (a == 0x80000000u && b == 0xFFFFFFFFu) TRAP(E_IntegerOverflow);
        TOP() = u32c((uint32_t)((int32_t)a / (int32_t)b));
        pc++;
        break;
      }
      case OP_i32_div_u: {
        uint32_t b = (uint32_t)POP(), a = (uint32_t)TOP();
        if (b == 0) TRAP(E_DivideByZero);
        TOP() = u32c(a / b);
        pc++;
        break;
      }
      case OP_i32_rem_s: {
        uint32_t b = (uint32_t)POP(), a = (uint32_t)TOP();
        if (b == 0) TRAP(E_DivideByZero);
        if (a == 0x80000000u && b == 0xFFFFFFFFu)
          TOP() = 0;
        else
          TOP() = u32c((uint32_t)((int32_t)a % (int32_t)b));
        pc++;
        break;
      }
      case OP_i32_rem_u: {
        uint32_t b = (uint32_t)POP(), a = (uint32_t)TOP();
        if (b == 0) TRAP(E_DivideByZero);
        TOP() = u32c(a % b);
        pc++;
        break;
      }
      case OP_i32_eqz: TOP() = (uint32_t)TOP() == 0 ? 1 : 0; pc++; break;
      case OP_i32_eq: BIN32(a == b ? 1 : 0); pc++; break;
      case OP_i32_ne: BIN32(a != b ? 1 : 0); pc++; break;
      case OP_i32_lt_s: BIN32((int32_t)a < (int32_t)b ? 1 : 0); pc++; break;
      case OP_i32_lt_u: BIN32(a < b ? 1 : 0); pc++; break;
      case OP_i32_gt_s: BIN32((int32_t)a > (int32_t)b ? 1 : 0); pc++; break;
      case OP_i32_gt_u: BIN32(a > b ? 1 : 0); pc++; break;
      case OP_i32_le_s: BIN32((int32_t)a <= (int32_t)b ? 1 : 0); pc++; break;
      case OP_i32_le_u: BIN32(a <= b ? 1 : 0); pc++; break;
      case OP_i32_ge_s: BIN32((int32_t)a >= (int32_t)b ? 1 : 0); pc++; break;
      case OP_i32_ge_u: BIN32(a >= b ? 1 : 0); pc++; break;
      case OP_i32_clz: {
        uint32_t a = (uint32_t)TOP();
        TOP() = a ? __builtin_clz(a) : 32;
        pc++;
        break;
      }
      case OP_i32_ctz: {
        uint32_t a = (uint32_t)TOP();
        TOP() = a ? __builtin_ctz(a) : 32;
        pc++;
        break;
      }
      case OP_i32_popcnt:
        TOP() = __builtin_popcount((uint32_t)TOP());
        pc++;
        break;
      case OP_i32_extend8_s:
        TOP() = u32c((uint32_t)(int32_t)(int8_t)TOP());
        pc++;
        break;
      case OP_i32_extend16_s:
        TOP() = u32c((uint32_t)(int32_t)(int16_t)TOP());
        pc++;
        break;

      // ---- i64 numerics --------------------------------------------
      case OP_i64_add: BIN64(a + b); pc++; break;
      case OP_i64_sub: BIN64(a - b); pc++; break;
      case OP_i64_mul: BIN64(a * b); pc++; break;
      case OP_i64_and: BIN64(a & b); pc++; break;
      case OP_i64_or: BIN64(a | b); pc++; break;
      case OP_i64_xor: BIN64(a ^ b); pc++; break;
      case OP_i64_shl: BIN64(a << (b & 63)); pc++; break;
      case OP_i64_shr_u: BIN64(a >> (b & 63)); pc++; break;
      case OP_i64_shr_s: BIN64((uint64_t)((int64_t)a >> (b & 63))); pc++; break;
      case OP_i64_rotl: BIN64((b & 63) ? ((a << (b & 63)) | (a >> (64 - (b & 63)))) : a); pc++; break;
      case OP_i64_rotr: BIN64((b & 63) ? ((a >> (b & 63)) | (a << (64 - (b & 63)))) : a); pc++; break;
      case OP_i64_div_s: {
        cell b = POP(), a = TOP();
        if (b == 0) TRAP(E_DivideByZero);
        if (a == 0x8000000000000000ull && b == 0xFFFFFFFFFFFFFFFFull)
          TRAP(E_IntegerOverflow);
        TOP() = (cell)((int64_t)a / (int64_t)b);
        pc++;
        break;
      }
      case OP_i64_div_u: {
        cell b = POP(), a = TOP();
        if (b == 0) TRAP(E_DivideByZero);
        TOP() = a / b;
        pc++;
        break;
      }
      case OP_i64_rem_s: {
        cell b = POP(), a = TOP();
        if (b == 0) TRAP(E_DivideByZero);
        if (a == 0x8000000000000000ull && b == 0xFFFFFFFFFFFFFFFFull)
          TOP() = 0;
        else
          TOP() = (cell)((int64_t)a % (int64_t)b);
        pc++;
        break;
      }
      case OP_i64_rem_u: {
        cell b = POP(), a = TOP();
        if (b == 0) TRAP(E_DivideByZero);
        TOP() = a % b;
        pc++;
        break;
      }
      case OP_i64_eqz: TOP() = TOP() == 0 ? 1 : 0; pc++; break;
      case OP_i64_eq: BIN64(a == b ? 1 : 0); pc++; break;
      case OP_i64_ne: BIN64(a != b ? 1 : 0); pc++; break;
      case OP_i64_lt_s: BIN64((int64_t)a < (int64_t)b ? 1 : 0); pc++; break;
      case OP_i64_lt_u: BIN64(a < b ? 1 : 0); pc++; break;
      case OP_i64_gt_s: BIN64((int64_t)a > (int64_t)b ? 1 : 0); pc++; break;
      case OP_i64_gt_u: BIN64(a > b ? 1 : 0); pc++; break;
      case OP_i64_le_s: BIN64((int64_t)a <= (int64_t)b ? 1 : 0); pc++; break;
      case OP_i64_le_u: BIN64(a <= b ? 1 : 0); pc++; break;
      case OP_i64_ge_s: BIN64((int64_t)a >= (int64_t)b ? 1 : 0); pc++; break;
      case OP_i64_ge_u: BIN64(a >= b ? 1 : 0); pc++; break;
      case OP_i64_clz: {
        cell a = TOP();
        TOP() = a ? __builtin_clzll(a) : 64;
        pc++;
        break;
      }
      case OP_i64_ctz: {
        cell a = TOP();
        TOP() = a ? __builtin_ctzll(a) : 64;
        pc++;
        break;
      }
      case OP_i64_popcnt:
        TOP() = __builtin_popcountll(TOP());
        pc++;
        break;
      case OP_i64_extend8_s:
        TOP() = (cell)(int64_t)(int8_t)TOP();
        pc++;
        break;
      case OP_i64_extend16_s:
        TOP() = (cell)(int64_t)(int16_t)TOP();
        pc++;
        break;
      case OP_i64_extend32_s:
        TOP() = (cell)(int64_t)(int32_t)TOP();
        pc++;
        break;

      // ---- conversions ---------------------------------------------
      case OP_i32_wrap_i64: TOP() = (uint32_t)TOP(); pc++; break;
      case OP_i64_extend_i32_s: TOP() = (cell)(int64_t)s32(TOP()); pc++; break;
      case OP_i64_extend_i32_u: TOP() = (uint32_t)TOP(); pc++; break;
      case OP_i32_reinterpret_f32:
      case OP_f32_reinterpret_i32:
        pc++;
        break;  // raw cells already
      case OP_i64_reinterpret_f64:
      case OP_f64_reinterpret_i64:
        pc++;
        break;
      case OP_f32_convert_i32_s: TOP() = bits_f32((float)s32(TOP())); pc++; break;
      case OP_f32_convert_i32_u: TOP() = bits_f32((float)(uint32_t)TOP()); pc++; break;
      case OP_f32_convert_i64_s: TOP() = bits_f32((float)s64(TOP())); pc++; break;
      case OP_f32_convert_i64_u: TOP() = bits_f32((float)(uint64_t)TOP()); pc++; break;
      case OP_f64_convert_i32_s: TOP() = bits_f64((double)s32(TOP())); pc++; break;
      case OP_f64_convert_i32_u: TOP() = bits_f64((double)(uint32_t)TOP()); pc++; break;
      case OP_f64_convert_i64_s: TOP() = bits_f64((double)s64(TOP())); pc++; break;
      case OP_f64_convert_i64_u: TOP() = bits_f64((double)(uint64_t)TOP()); pc++; break;
      case OP_f32_demote_f64: TOP() = canon32(bits_f32((float)f64_of(TOP()))); pc++; break;
      case OP_f64_promote_f32: TOP() = canon64(bits_f64((double)f32_of(TOP()))); pc++; break;

#define TRUNC(fty_of, lo, hi, mask)                    \
  do {                                                 \
    double v = (double)fty_of(TOP());                  \
    if (std::isnan(v)) TRAP(E_InvalidConvToInt);       \
    double t = std::trunc(v);                          \
    if (!((lo) < t && t < (hi))) TRAP(E_IntegerOverflow); \
    TOP() = (cell)(((t) < 0 ? (uint64_t)(int64_t)t : (uint64_t)t)) & (mask); \
  } while (0)
#define TRUNC_SAT(fty_of, lo, hi, lo_res, hi_res, mask)  \
  do {                                                   \
    double v = (double)fty_of(TOP());                    \
    if (std::isnan(v)) {                                 \
      TOP() = 0;                                         \
    } else {                                             \
      double t = std::trunc(v);                          \
      if (t <= (lo))                                     \
        TOP() = (cell)(lo_res) & (mask);                 \
      else if (t >= (hi))                                \
        TOP() = (cell)(hi_res) & (mask);                 \
      else                                               \
        TOP() = (cell)(((t) < 0 ? (uint64_t)(int64_t)t : (uint64_t)t)) & (mask); \
    }                                                    \
  } while (0)

      case OP_i32_trunc_f32_s: TRUNC(f32_of, -2147483649.0, 2147483648.0, 0xFFFFFFFFull); pc++; break;
      case OP_i32_trunc_f32_u: TRUNC(f32_of, -1.0, 4294967296.0, 0xFFFFFFFFull); pc++; break;
      case OP_i32_trunc_f64_s: TRUNC(f64_of, -2147483649.0, 2147483648.0, 0xFFFFFFFFull); pc++; break;
      case OP_i32_trunc_f64_u: TRUNC(f64_of, -1.0, 4294967296.0, 0xFFFFFFFFull); pc++; break;
      case OP_i64_trunc_f32_s: TRUNC(f32_of, -9223372036854777856.0, 9223372036854775808.0, ~0ull); pc++; break;
      case OP_i64_trunc_f32_u: TRUNC(f32_of, -1.0, 18446744073709551616.0, ~0ull); pc++; break;
      case OP_i64_trunc_f64_s: TRUNC(f64_of, -9223372036854777856.0, 9223372036854775808.0, ~0ull); pc++; break;
      case OP_i64_trunc_f64_u: TRUNC(f64_of, -1.0, 18446744073709551616.0, ~0ull); pc++; break;
      case OP_i32_trunc_sat_f32_s: TRUNC_SAT(f32_of, -2147483649.0, 2147483648.0, (uint64_t)(uint32_t)INT32_MIN, (uint64_t)INT32_MAX, 0xFFFFFFFFull); pc++; break;
      case OP_i32_trunc_sat_f32_u: TRUNC_SAT(f32_of, -1.0, 4294967296.0, 0, 0xFFFFFFFFull, 0xFFFFFFFFull); pc++; break;
      case OP_i32_trunc_sat_f64_s: TRUNC_SAT(f64_of, -2147483649.0, 2147483648.0, (uint64_t)(uint32_t)INT32_MIN, (uint64_t)INT32_MAX, 0xFFFFFFFFull); pc++; break;
      case OP_i32_trunc_sat_f64_u: TRUNC_SAT(f64_of, -1.0, 4294967296.0, 0, 0xFFFFFFFFull, 0xFFFFFFFFull); pc++; break;
      case OP_i64_trunc_sat_f32_s: TRUNC_SAT(f32_of, -9223372036854777856.0, 9223372036854775808.0, (uint64_t)INT64_MIN, (uint64_t)INT64_MAX, ~0ull); pc++; break;
      case OP_i64_trunc_sat_f32_u: TRUNC_SAT(f32_of, -1.0, 18446744073709551616.0, 0, ~0ull, ~0ull); pc++; break;
      case OP_i64_trunc_sat_f64_s: TRUNC_SAT(f64_of, -9223372036854777856.0, 9223372036854775808.0, (uint64_t)INT64_MIN, (uint64_t)INT64_MAX, ~0ull); pc++; break;
      case OP_i64_trunc_sat_f64_u: TRUNC_SAT(f64_of, -1.0, 18446744073709551616.0, 0, ~0ull, ~0ull); pc++; break;

      // ---- f32 ------------------------------------------------------
      case OP_f32_add: FBIN32(a + b); pc++; break;
      case OP_f32_sub: FBIN32(a - b); pc++; break;
      case OP_f32_mul: FBIN32(a * b); pc++; break;
      case OP_f32_div: FBIN32(a / b); pc++; break;
      case OP_f32_eq: FCMP32(a == b); pc++; break;
      case OP_f32_ne: FCMP32(!(a == b)); pc++; break;
      case OP_f32_lt: FCMP32(a < b); pc++; break;
      case OP_f32_gt: FCMP32(a > b); pc++; break;
      case OP_f32_le: FCMP32(a <= b); pc++; break;
      case OP_f32_ge: FCMP32(a >= b); pc++; break;
      case OP_f32_abs: TOP() = TOP() & 0x7FFFFFFFull; pc++; break;
      case OP_f32_neg: TOP() = TOP() ^ 0x80000000ull; pc++; break;
      case OP_f32_copysign: {
        cell b = POP();
        TOP() = (TOP() & 0x7FFFFFFFull) | (b & 0x80000000ull);
        pc++;
        break;
      }
      case OP_f32_min:
      case OP_f32_max: {
        cell bbits = POP(), abits = TOP();
        float a = f32_of(abits), b = f32_of(bbits);
        if (std::isnan(a) || std::isnan(b)) {
          TOP() = 0x7FC00000ull;
        } else if (a == b) {
          bool sa = (abits >> 31) & 1;
          if (op == OP_f32_min)
            TOP() = sa ? abits : bbits;
          else
            TOP() = sa ? bbits : abits;
        } else {
          bool take_a = (a < b) == (op == OP_f32_min);
          TOP() = take_a ? abits : bbits;
        }
        pc++;
        break;
      }
      case OP_f32_ceil: FUN32(std::ceil(a)); pc++; break;
      case OP_f32_floor: FUN32(std::floor(a)); pc++; break;
      case OP_f32_trunc: FUN32(std::trunc(a)); pc++; break;
      case OP_f32_nearest: FUN32(std::nearbyint(a)); pc++; break;
      case OP_f32_sqrt: FUN32(std::sqrt(a)); pc++; break;

      // ---- f64 ------------------------------------------------------
      case OP_f64_add: FBIN64(a + b); pc++; break;
      case OP_f64_sub: FBIN64(a - b); pc++; break;
      case OP_f64_mul: FBIN64(a * b); pc++; break;
      case OP_f64_div: FBIN64(a / b); pc++; break;
      case OP_f64_eq: FCMP64(a == b); pc++; break;
      case OP_f64_ne: FCMP64(!(a == b)); pc++; break;
      case OP_f64_lt: FCMP64(a < b); pc++; break;
      case OP_f64_gt: FCMP64(a > b); pc++; break;
      case OP_f64_le: FCMP64(a <= b); pc++; break;
      case OP_f64_ge: FCMP64(a >= b); pc++; break;
      case OP_f64_abs: TOP() = TOP() & 0x7FFFFFFFFFFFFFFFull; pc++; break;
      case OP_f64_neg: TOP() = TOP() ^ 0x8000000000000000ull; pc++; break;
      case OP_f64_copysign: {
        cell b = POP();
        TOP() = (TOP() & 0x7FFFFFFFFFFFFFFFull) | (b & 0x8000000000000000ull);
        pc++;
        break;
      }
      case OP_f64_min:
      case OP_f64_max: {
        cell bbits = POP(), abits = TOP();
        double a = f64_of(abits), b = f64_of(bbits);
        if (std::isnan(a) || std::isnan(b)) {
          TOP() = 0x7FF8000000000000ull;
        } else if (a == b) {
          bool sa = (abits >> 63) & 1;
          if (op == OP_f64_min)
            TOP() = sa ? abits : bbits;
          else
            TOP() = sa ? bbits : abits;
        } else {
          bool take_a = (a < b) == (op == OP_f64_min);
          TOP() = take_a ? abits : bbits;
        }
        pc++;
        break;
      }
      case OP_f64_ceil: FUN64(std::ceil(a)); pc++; break;
      case OP_f64_floor: FUN64(std::floor(a)); pc++; break;
      case OP_f64_trunc: FUN64(std::trunc(a)); pc++; break;
      case OP_f64_nearest: FUN64(std::nearbyint(a)); pc++; break;
      case OP_f64_sqrt: FUN64(std::sqrt(a)); pc++; break;

      default:
        TRAP(E_ExecutionFailed);
    }
  }

done:
  *retired_out = retired;
  *out_pages = cur_pages;
  if (tsize_io) *tsize_io = tsize;
  delete[] st;
  delete[] frames;
  return trapcode;
}
