// C++ PJRT executor host: compile + run XLA programs with zero Python in
// the execution path.
//
// This is the native counterpart of the role libtensorflow played for the
// reference (graph import + session execution via JNI,
// TensorFlowOps.scala:76-95): it dlopens any PJRT plugin (an accelerator
// plugin, or the repo CPU plugin for tests), creates a client, compiles MLIR
// (StableHLO) programs, stages host buffers into device memory, executes,
// and reads results back — all through the stable PJRT C API
// (SURVEY.md §2.4: "C++ PJRT-based executor ... the single largest build
// item").
//
// Exposed as a C ABI for ctypes (tensorframes_tpu/runtime/pjrt_host.py).
// Single-device execution per call; multi-device programs go through the
// JAX path (parallel/).

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

struct Ctx {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  std::vector<PJRT_Device*> devices;
  std::string platform;
};

struct OutSet {
  std::vector<PJRT_Buffer*> buffers;
};

bool check(const PJRT_Api* api, PJRT_Error* e, char* err, size_t errlen) {
  if (e == nullptr) return true;
  PJRT_Error_Message_Args m;
  m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  m.extension_start = nullptr;
  m.error = e;
  api->PJRT_Error_Message(&m);
  snprintf(err, errlen, "%.*s", static_cast<int>(m.message_size), m.message);
  PJRT_Error_Destroy_Args d;
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.extension_start = nullptr;
  d.error = e;
  api->PJRT_Error_Destroy(&d);
  return false;
}

bool await_event(const PJRT_Api* api, PJRT_Event* ev, char* err,
                 size_t errlen) {
  PJRT_Event_Await_Args a;
  a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  a.extension_start = nullptr;
  a.event = ev;
  bool ok = check(api, api->PJRT_Event_Await(&a), err, errlen);
  PJRT_Event_Destroy_Args d;
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.extension_start = nullptr;
  d.event = ev;
  api->PJRT_Event_Destroy(&d);
  return ok;
}

}  // namespace

extern "C" {

// Load a PJRT plugin and create a client. Returns Ctx* or nullptr.
// Create options (plugin-specific NamedValues): n_options entries;
// types[i] 0 = string (str_vals[i]), 1 = int64 (int_vals[i]).
void* tfs_pjrt_load(const char* so_path, const char** opt_keys,
                    const int32_t* opt_types, const char** opt_strs,
                    const int64_t* opt_ints, int64_t n_options, char* err,
                    size_t errlen) {
  void* dl = dlopen(so_path, RTLD_NOW | RTLD_LOCAL);
  if (!dl) {
    snprintf(err, errlen, "dlopen failed: %s", dlerror());
    return nullptr;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(dl, "GetPjrtApi"));
  if (!get_api) {
    snprintf(err, errlen, "plugin has no GetPjrtApi symbol");
    dlclose(dl);
    return nullptr;
  }
  const PJRT_Api* api = get_api();
  auto* ctx = new Ctx();
  ctx->dl = dl;
  ctx->api = api;

  PJRT_Plugin_Initialize_Args init;
  init.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  init.extension_start = nullptr;
  if (!check(api, api->PJRT_Plugin_Initialize(&init), err, errlen)) {
    delete ctx;
    return nullptr;
  }

  std::vector<PJRT_NamedValue> options(n_options);
  for (int64_t i = 0; i < n_options; i++) {
    PJRT_NamedValue& v = options[i];
    std::memset(&v, 0, sizeof(v));
    v.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    v.name = opt_keys[i];
    v.name_size = std::strlen(opt_keys[i]);
    if (opt_types[i] == 0) {
      v.type = PJRT_NamedValue_kString;
      v.string_value = opt_strs[i];
      v.value_size = std::strlen(opt_strs[i]);
    } else {
      v.type = PJRT_NamedValue_kInt64;
      v.int64_value = opt_ints[i];
      v.value_size = 1;
    }
  }

  PJRT_Client_Create_Args c;
  std::memset(&c, 0, sizeof(c));
  c.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  c.create_options = options.data();
  c.num_options = static_cast<size_t>(n_options);
  if (!check(api, api->PJRT_Client_Create(&c), err, errlen)) {
    delete ctx;
    return nullptr;
  }
  ctx->client = c.client;

  PJRT_Client_AddressableDevices_Args d;
  d.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  d.extension_start = nullptr;
  d.client = ctx->client;
  if (!check(api, api->PJRT_Client_AddressableDevices(&d), err, errlen)) {
    delete ctx;
    return nullptr;
  }
  ctx->devices.assign(d.addressable_devices,
                      d.addressable_devices + d.num_addressable_devices);

  PJRT_Client_PlatformName_Args p;
  p.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  p.extension_start = nullptr;
  p.client = ctx->client;
  if (check(api, api->PJRT_Client_PlatformName(&p), err, errlen)) {
    ctx->platform.assign(p.platform_name, p.platform_name_size);
  }
  return ctx;
}

void tfs_pjrt_destroy(void* h) {
  auto* ctx = static_cast<Ctx*>(h);
  if (ctx->client) {
    PJRT_Client_Destroy_Args d;
    d.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    d.extension_start = nullptr;
    d.client = ctx->client;
    ctx->api->PJRT_Client_Destroy(&d);
  }
  // NB: we do not dlclose — plugin teardown at process exit is safer.
  delete ctx;
}

const char* tfs_pjrt_platform(void* h) {
  return static_cast<Ctx*>(h)->platform.c_str();
}

int64_t tfs_pjrt_device_count(void* h) {
  return static_cast<Ctx*>(h)->devices.size();
}

// Compile an MLIR (StableHLO) module. compile_options: serialized
// CompileOptionsProto bytes (produced by the Python side).
void* tfs_pjrt_compile(void* h, const char* code, size_t code_size,
                       const char* options, size_t options_size, char* err,
                       size_t errlen) {
  auto* ctx = static_cast<Ctx*>(h);
  PJRT_Program prog;
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.extension_start = nullptr;
  prog.code = const_cast<char*>(code);
  prog.code_size = code_size;
  prog.format = "mlir";
  prog.format_size = 4;

  PJRT_Client_Compile_Args a;
  a.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  a.extension_start = nullptr;
  a.client = ctx->client;
  a.program = &prog;
  a.compile_options = options;
  a.compile_options_size = options_size;
  if (!check(ctx->api, ctx->api->PJRT_Client_Compile(&a), err, errlen)) {
    return nullptr;
  }
  return a.executable;
}

void tfs_pjrt_executable_free(void* h, void* exec) {
  auto* ctx = static_cast<Ctx*>(h);
  PJRT_LoadedExecutable_Destroy_Args d;
  d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
  d.extension_start = nullptr;
  d.executable = static_cast<PJRT_LoadedExecutable*>(exec);
  ctx->api->PJRT_LoadedExecutable_Destroy(&d);
}

int64_t tfs_pjrt_num_outputs(void* h, void* exec, char* err, size_t errlen) {
  auto* ctx = static_cast<Ctx*>(h);
  PJRT_LoadedExecutable_GetExecutable_Args g;
  g.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  g.extension_start = nullptr;
  g.loaded_executable = static_cast<PJRT_LoadedExecutable*>(exec);
  if (!check(ctx->api, ctx->api->PJRT_LoadedExecutable_GetExecutable(&g), err,
             errlen))
    return -1;
  PJRT_Executable_NumOutputs_Args n;
  n.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  n.extension_start = nullptr;
  n.executable = g.executable;
  if (!check(ctx->api, ctx->api->PJRT_Executable_NumOutputs(&n), err, errlen))
    return -1;
  return static_cast<int64_t>(n.num_outputs);
}

// Execute on device 0. Inputs are dense host arrays (row-major):
//   datas[i], with dims at dims_flat[dim_offsets[i] .. +ndims[i]],
//   element type types[i] (PJRT_Buffer_Type ordinal).
// Returns an OutSet* holding the output device buffers (query sizes with
// tfs_pjrt_output_size, copy out with tfs_pjrt_output_read).
void* tfs_pjrt_execute(void* h, void* exec, int64_t num_args,
                       const void** datas, const int64_t* dims_flat,
                       const int64_t* dim_offsets, const int64_t* ndims,
                       const int32_t* types, char* err, size_t errlen) {
  auto* ctx = static_cast<Ctx*>(h);
  const PJRT_Api* api = ctx->api;
  std::vector<PJRT_Buffer*> args_bufs;
  args_bufs.reserve(num_args);
  auto cleanup_args = [&]() {
    for (auto* b : args_bufs) {
      PJRT_Buffer_Destroy_Args d;
      d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      d.extension_start = nullptr;
      d.buffer = b;
      api->PJRT_Buffer_Destroy(&d);
    }
  };

  for (int64_t i = 0; i < num_args; i++) {
    PJRT_Client_BufferFromHostBuffer_Args b;
    std::memset(&b, 0, sizeof(b));
    b.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    b.client = ctx->client;
    b.data = datas[i];
    b.type = static_cast<PJRT_Buffer_Type>(types[i]);
    b.dims = dims_flat + dim_offsets[i];
    b.num_dims = static_cast<size_t>(ndims[i]);
    b.host_buffer_semantics = PJRT_HostBufferSemantics_kImmutableOnlyDuringCall;
    b.device = ctx->devices[0];
    if (!check(api, api->PJRT_Client_BufferFromHostBuffer(&b), err, errlen)) {
      cleanup_args();
      return nullptr;
    }
    if (b.done_with_host_buffer != nullptr &&
        !await_event(api, b.done_with_host_buffer, err, errlen)) {
      cleanup_args();
      return nullptr;
    }
    args_bufs.push_back(b.buffer);
  }

  int64_t num_outputs = tfs_pjrt_num_outputs(h, exec, err, errlen);
  if (num_outputs < 0) {
    cleanup_args();
    return nullptr;
  }

  std::vector<PJRT_Buffer*> outputs(num_outputs, nullptr);
  PJRT_Buffer** output_list = outputs.data();
  PJRT_Buffer* const* arg_list = args_bufs.data();
  PJRT_Event* done = nullptr;

  PJRT_ExecuteOptions opts;
  std::memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_LoadedExecutable_Execute_Args e;
  std::memset(&e, 0, sizeof(e));
  e.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  e.executable = static_cast<PJRT_LoadedExecutable*>(exec);
  e.options = &opts;
  e.argument_lists = &arg_list;
  e.num_devices = 1;
  e.num_args = static_cast<size_t>(num_args);
  e.output_lists = &output_list;
  e.device_complete_events = &done;
  bool ok = check(api, api->PJRT_LoadedExecutable_Execute(&e), err, errlen);
  if (ok && done != nullptr) ok = await_event(api, done, err, errlen);
  cleanup_args();
  if (!ok) {
    for (auto* b : outputs) {
      if (b == nullptr) continue;
      PJRT_Buffer_Destroy_Args d;
      d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      d.extension_start = nullptr;
      d.buffer = b;
      api->PJRT_Buffer_Destroy(&d);
    }
    return nullptr;
  }
  auto* out = new OutSet();
  out->buffers = std::move(outputs);
  return out;
}

int64_t tfs_pjrt_outset_count(void* outset) {
  return static_cast<OutSet*>(outset)->buffers.size();
}

namespace {

// Dense row-major host layout for a buffer (minor_to_major = [n-1..0]).
// Without this, ToHostBuffer copies in the buffer's DEVICE layout, which
// on TPU is not row-major (observed: transposed matmul results).
bool row_major_layout(const PJRT_Api* api, PJRT_Buffer* buf,
                      std::vector<int64_t>* m2m,
                      PJRT_Buffer_MemoryLayout* layout, char* err,
                      size_t errlen) {
  PJRT_Buffer_Dimensions_Args d;
  d.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  d.extension_start = nullptr;
  d.buffer = buf;
  if (!check(api, api->PJRT_Buffer_Dimensions(&d), err, errlen)) return false;
  m2m->resize(d.num_dims);
  for (size_t k = 0; k < d.num_dims; k++)
    (*m2m)[k] = static_cast<int64_t>(d.num_dims - 1 - k);
  std::memset(layout, 0, sizeof(*layout));
  layout->struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
  layout->type = PJRT_Buffer_MemoryLayout_Type_Tiled;
  layout->tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
  layout->tiled.minor_to_major = m2m->data();
  layout->tiled.minor_to_major_size = m2m->size();
  return true;
}

}  // namespace

// Required host size in bytes for output i (queried from the runtime).
int64_t tfs_pjrt_output_size(void* h, void* outset, int64_t i, char* err,
                             size_t errlen) {
  auto* ctx = static_cast<Ctx*>(h);
  auto* os = static_cast<OutSet*>(outset);
  std::vector<int64_t> m2m;
  PJRT_Buffer_MemoryLayout layout;
  if (!row_major_layout(ctx->api, os->buffers[i], &m2m, &layout, err, errlen))
    return -1;
  PJRT_Buffer_ToHostBuffer_Args a;
  std::memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  a.src = os->buffers[i];
  a.host_layout = &layout;
  a.dst = nullptr;
  if (!check(ctx->api, ctx->api->PJRT_Buffer_ToHostBuffer(&a), err, errlen))
    return -1;
  return static_cast<int64_t>(a.dst_size);
}

// Copy output i into dst (dst_size from tfs_pjrt_output_size) as dense
// row-major. Blocking.
int tfs_pjrt_output_read(void* h, void* outset, int64_t i, void* dst,
                         int64_t dst_size, char* err, size_t errlen) {
  auto* ctx = static_cast<Ctx*>(h);
  auto* os = static_cast<OutSet*>(outset);
  std::vector<int64_t> m2m;
  PJRT_Buffer_MemoryLayout layout;
  if (!row_major_layout(ctx->api, os->buffers[i], &m2m, &layout, err, errlen))
    return 1;
  PJRT_Buffer_ToHostBuffer_Args a;
  std::memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  a.src = os->buffers[i];
  a.host_layout = &layout;
  a.dst = dst;
  a.dst_size = static_cast<size_t>(dst_size);
  if (!check(ctx->api, ctx->api->PJRT_Buffer_ToHostBuffer(&a), err, errlen))
    return 1;
  if (a.event != nullptr && !await_event(ctx->api, a.event, err, errlen))
    return 1;
  return 0;
}

void tfs_pjrt_outset_free(void* h, void* outset) {
  auto* ctx = static_cast<Ctx*>(h);
  auto* os = static_cast<OutSet*>(outset);
  for (auto* b : os->buffers) {
    PJRT_Buffer_Destroy_Args d;
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.extension_start = nullptr;
    d.buffer = b;
    ctx->api->PJRT_Buffer_Destroy(&d);
  }
  delete os;
}

}  // extern "C"
